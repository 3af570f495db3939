"""How unevenly the routing loads the held experts: the rows routed to the
most loaded (MoE layer, held expert) over the mean of all of them, summed
over the run, set-up included (1 is an even load).

Read from the program's ``moe`` counter group (``repro.tracing``): the
trainer adds ``("rows", layer, expert)`` once a step, from the values the
ledger record's fetch brings. A program without that group, or a run
that routed no row, reads nothing."""


def read(obs):
    try:
        from repro.tracing import counters
    except ImportError:
        return None
    group = counters().get("moe") or {}
    rows = [n for k, n in group.items()
            if isinstance(k, tuple) and k and k[0] == "rows"]
    if not rows or sum(rows) == 0:
        return None
    return max(rows) / (sum(rows) / len(rows))
