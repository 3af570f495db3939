"""Recovery's slot reads (the program's ``adcc.recover.read`` spans:
``np.load`` of every file of each slot scanned, the torn slot's
included), summed per restart cycle, mean over the window's cycles.

Read from a traced run. The window's cycles are the last
``len(restore_s)`` ``adcc.recover`` roots of the program's record (the
warm-up cycle's root comes before them); a read belongs to the root
whose ``restart`` ordinal it carries. A program without these spans
reads nothing."""

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
    reads = spans("adcc.recover.read")
    per = [[s.seconds for s in reads
            if s.attrs.get("restart") == r.attrs.get("restart")]
           for r in roots]
    if len(roots) < n or not all(per):
        return None
    return statistics.fmean(sum(p) for p in per)
