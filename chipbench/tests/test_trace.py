"""The reduction from a profiler trace to busy time, idle share and
labelled idle gaps."""

from __future__ import annotations

import pytest

from _tiny import ROOT

from chipbench import trace


def test_union_clip_and_gaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    busy = trace.union(trace.clip([(0, 2), (4, 6), (9, 12)], 1, 10))
    assert busy == [(1, 2), (4, 6), (9, 10)]
    assert trace.gaps(busy, 0, 11) == [(0, 1), (2, 4), (6, 9), (10, 11)]


def test_gap_takes_the_innermost_open_annotation():
    anns = [("_run_program", 0, 100), ("build_static", 10, 20),
            ("_assemble", 60, 90)]
    assert trace.label((12, 18), anns) == "build_static"
    assert trace.label((30, 40), anns) == "_run_program"
    assert trace.label((70, 80), anns) == "_assemble"
    assert trace.label((120, 130), anns) == trace.OTHER


def test_reduce_synthetic_trace():
    ns = 1_000_000
    anns = [(trace.REQUEST, 0, 100 * ns), (trace.REQUEST, 100 * ns, 200 * ns),
            ("build_static", 0, 30 * ns), ("_assemble", 170 * ns, 200 * ns)]
    ops = [(0, "scan", 40 * ns, 80 * ns), (0, "sort", 60 * ns, 90 * ns),
           (0, "scan", 120 * ns, 160 * ns),
           (0, "copy", 250 * ns, 260 * ns)]      # after the window: dropped
    red = trace.reduce(trace.Trace(ops=ops, annotations=anns, n_devices=1))
    assert red.window_s == pytest.approx(0.2)
    assert red.busy_s == pytest.approx(0.09)     # 40..90 and 120..160
    assert red.n_requests == 2
    assert red.device_ops == [["scan", pytest.approx(0.08)],
                              ["sort", pytest.approx(0.03)]]
    assert red.idle_gaps[0] == ["build_static", pytest.approx(0.04)]
    assert red.idle_gaps[1] == ["_assemble", pytest.approx(0.04)]
    assert sorted(g[1] for g in red.idle_gaps) == pytest.approx(
        [0.03, 0.04, 0.04])


def test_reduce_without_device_ops_reads_nothing():
    tr = trace.Trace(ops=[], annotations=[(trace.REQUEST, 0, 10)],
                     n_devices=0)
    assert trace.reduce(tr) is None


def test_recorded_tpu_trace():
    """Two requests of a tiny cell (MSS, 2 x 2 clients, 32 messages, 2
    lanes) profiled on one TPU v5 lite with the layer spans on."""
    from chipbench import run
    path = ROOT / "chipbench/tests/data/tiny.xplane.pb.gz"
    tr = trace.read_xplane(str(path), run.SPANS)
    assert tr.n_devices == 1
    assert {a[0] for a in tr.annotations} == set(run.SPANS) | {trace.REQUEST}
    red = trace.reduce(tr)
    assert red.n_requests == 2
    assert 0 < red.busy_s < red.window_s
    names = [n for n, _ in red.device_ops]
    assert all(" = " not in n and not n.startswith("%") for n in names)
    assert {g[0] for g in red.idle_gaps} <= set(run.SPANS) | {trace.OTHER}
    # busy is the union of the op intervals, not their sum
    total = sum(e - s for _, _, s, e in tr.ops) * 1e-9
    assert red.busy_s < total
