"""The trace reduction, on interval arithmetic and on a small trace
recorded on the CPU (three jitted matrix products inside ``bench.window``,
each followed by a host wait in ``bench.after_step``)."""

import os

import pytest

from bench import trace as TR

DATA = os.path.join(os.path.dirname(__file__), "data", "cpu_small.xplane.pb")
# the names a CPU trace uses for what a TPU trace calls device operations
CPU = dict(device_plane="/host:CPU", op_line="tf_XLAPjRtCpuClient",
           exclude=r"(ThreadpoolListener|SlinkyThreadPool|ThunkExecutor|end: )")


@pytest.mark.parametrize("intervals, merged", [
    ([], []),
    ([(0, 5)], [(0, 5)]),
    ([(0, 5), (3, 8)], [(0, 8)]),
    ([(4, 6), (0, 2)], [(0, 2), (4, 6)]),
    ([(0, 10), (2, 3), (5, 12)], [(0, 12)]),
    ([(0, 2), (2, 4)], [(0, 4)]),
])
def test_merge(intervals, merged):
    assert TR.merge(intervals) == merged


@pytest.mark.parametrize("busy, lo, hi, gaps", [
    ([], 0, 10, [(0, 10)]),
    ([(0, 10)], 0, 10, []),
    ([(2, 4), (6, 7)], 0, 10, [(0, 2), (4, 6), (7, 10)]),
])
def test_gaps(busy, lo, hi, gaps):
    assert TR.gaps(busy, lo, hi) == gaps


def test_clip_drops_what_lies_outside():
    assert TR.clip([(0, 5), (8, 12), (20, 30)], 3, 10) == [(3, 5), (8, 10)]


def test_attribute_gives_each_gap_to_the_span_that_overlaps_most():
    spans = [("bench.a", 0, 6), ("bench.b", 5, 20)]
    got = TR.attribute([(0, 4), (4, 10), (30, 40)], spans)
    assert got == pytest.approx({"bench.a": 4e-9, "bench.b": 6e-9,
                                 "unattributed": 10e-9})


def test_reduce_recorded_cpu_trace():
    out = TR.reduce(DATA, **CPU)
    assert out["devices"] == 1
    assert 0.0 < out["busy_s"] < out["window_s"]
    # the window is the bench.window span, some 60 ms of host waits
    assert 0.05 < out["window_s"] < 1.0
    names = [name for name, _ in out["device_ops"]]
    assert names[0].startswith("dot_general")
    assert all(s > 0 for _, s in out["device_ops"])
    gaps = dict(out["idle_gaps"])
    assert max(gaps, key=gaps.get) == "bench.after_step"
    # idle and busy time add up to the window
    assert sum(gaps.values()) + out["busy_s"] == pytest.approx(
        out["window_s"], rel=1e-6)


def test_reduce_finds_no_tpu_plane_in_a_cpu_trace():
    out = TR.reduce(DATA)
    assert out["busy_s"] == 0.0 and out["devices"] == 0


def test_find_xplane(tmp_path):
    assert TR.find_xplane(str(tmp_path)) is None
    d = tmp_path / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"")
    assert TR.find_xplane(str(tmp_path)).endswith("host.xplane.pb")
