"""The resumed step's dispatch (the program's ``train.dispatch`` spans:
the call of the step function until it returns, which on a restart
retraces, lowers and loads the step from the compile cache), summed per
restart cycle, mean over the window's cycles.

Read from a traced run. The window's cycles are the last
``len(restore_s)`` ``adcc.recover`` roots of the program's record (the
warm-up cycle's root comes before them); a dispatch belongs to the
cycle whose root ended last before it began, on the same thread. A
program without these spans reads nothing."""

import statistics


def read(obs):
    n = len(obs.get("restore_s") or ())
    if not obs.get("trace") or not n:
        return None
    try:
        from repro.tracing import spans
    except ImportError:
        return None
    roots = sorted(spans("adcc.recover"), key=lambda s: s.start_ns)[-n:]
    dispatches = spans("train.dispatch")
    ends = [r.start_ns for r in roots[1:]] + [float("inf")]
    per = [[s.seconds for s in dispatches
            if s.thread == r.thread and r.end_ns <= s.start_ns < end]
           for r, end in zip(roots, ends)]
    if len(roots) < n or not all(per):
        return None
    return statistics.fmean(sum(p) for p in per)
