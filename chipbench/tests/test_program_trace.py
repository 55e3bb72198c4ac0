"""Idle time by the program's own spans, device time by the scan step's
named scopes, and the recorder's clock against the trace's, on
synthetic traces and on a fixture profiled on the chip."""

from __future__ import annotations

import json

import pytest

from _tiny import ROOT

from chipbench import program_trace as pt
from chipbench import trace

FIXTURE = ROOT / "chipbench/tests/data"


def test_idle_by_span_splits_gaps_at_span_boundaries():
    """Each idle nanosecond goes to the innermost program span open at
    that instant; a hole that crosses a boundary is split there."""
    spans = [("streamsim.run_many", 10, 100),
             ("streamsim.build_static", 20, 40),
             ("streamsim.program", 50, 90), ("streamsim.wait", 60, 80)]
    holes = [(0, 30), (45, 70), (85, 120)]
    got = pt.idle_by_span(holes, spans)
    want = {pt.OTHER: (10 + 20) * 1e-9,
            "streamsim.run_many": (10 + 5 + 10) * 1e-9,
            "streamsim.build_static": 10 * 1e-9,
            "streamsim.program": (10 + 5) * 1e-9,
            "streamsim.wait": 10 * 1e-9}
    assert got.keys() == want.keys()
    for k in want:
        assert got[k] == pytest.approx(want[k]), k
    assert sum(got.values()) == pytest.approx(
        sum(e - s for s, e in holes) * 1e-9)
    assert pt.idle_by_span(holes, []) == {pt.OTHER: pytest.approx(90e-9)}


def test_idle_by_span_sums_to_the_window_idle_time():
    ns = 1_000_000
    tr = trace.Trace(ops=[(0, "scan", 40 * ns, 80 * ns)],
                     annotations=[(trace.REQUEST, 0, 100 * ns)], n_devices=1)
    spans = [("streamsim.build_static", 0, 30 * ns),
             ("streamsim.program", 35 * ns, 95 * ns)]
    holes = pt.window_holes(tr)
    assert holes == [(0, 40 * ns), (80 * ns, 100 * ns)]
    by_span = pt.idle_by_span(holes, spans)
    assert by_span == {"streamsim.build_static": pytest.approx(0.03),
                       pt.OTHER: pytest.approx(0.01),
                       "streamsim.program": pytest.approx(0.02)}
    red = trace.reduce(tr)
    assert sum(by_span.values()) == pytest.approx(red.window_s - red.busy_s)


def test_scopes_of_compiled_hlo_text():
    text = "\n".join([
        'ENTRY %main {',
        '  %fusion.3 = f32[2] fusion(%p), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(program)/while/body/closed_call/transit/'
        'while/body/closed_call/add" source_file="x.py"}',
        '  ROOT %sort.1 = f32[2] sort(%p), metadata={op_name='
        '"jit(program)/while/body/closed_call/consume_ack/sort"}',
        '  %copy.2 = f32[2] copy(%p), metadata={op_name="jit(program)/'
        'while/body/dynamic_slice"}',
        '  %add.9 = f32[2] add(%p, %p)',
    ])
    assert pt.scopes_of(text) == {"fusion.3": "transit",
                                  "sort.1": "consume_ack"}


def test_scan_phases_pairs_op_names_with_their_program():
    """Op names repeat across programs: each program execution in the
    window reads its own map; loops are not leaf ops."""
    ns = 1000
    anns = [(trace.REQUEST, 0, 100 * ns)]
    mods = [(0, "jit_program(1)", 10 * ns, 40 * ns),
            (0, "jit_program(2)", 50 * ns, 90 * ns)]
    ops = [(0, "while.1", 10 * ns, 40 * ns),
           (0, "fusion.1", 12 * ns, 20 * ns),
           (0, "fusion.2", 20 * ns, 25 * ns),
           (0, "fusion.1", 50 * ns, 70 * ns),
           (0, "copy.1", 70 * ns, 75 * ns)]
    tr = trace.Trace(ops=ops, annotations=anns, n_devices=1)
    scopes = [{"fusion.1": "transit", "fusion.2": "pump_assign"},
              {"fusion.1": "consume_ack"}]
    got = pt.scan_phases(tr, mods, scopes)
    assert got == {"transit": pytest.approx(8e-6),
                   "pump_assign": pytest.approx(5e-6),
                   "consume_ack": pytest.approx(20e-6),
                   pt.UNSCOPED: pytest.approx(5e-6)}
    assert pt.scan_phases(tr, mods, scopes[:1]) is None


def test_match_spans_pairs_by_name_and_order():
    mine = [("a", 0, 5), ("b", 1, 2), ("a", 10, 12)]
    theirs = [("a", 103, 105), ("a", 100, 100 + 5), ("c", 0, 1)]
    assert pt.match_spans(mine, theirs) == [
        (("a", 0, 5), ("a", 100, 105)), (("a", 10, 12), ("a", 103, 105))]
    assert pt.clock_error_ns(mine, theirs) == (2, 100, 7)
    assert pt.clock_error_ns(mine, []) == (0, 0, 0)


def scoped_fixture():
    """Two requests of a tiny cell (MSS, 2 x 2 clients, 32 messages, 2
    lanes) profiled on one TPU v5 lite with the program's own recorder
    on (``capture_scoped.py``): the trace, the program's part of it,
    and the recorder's spans, counters and per-program scope maps."""
    from chipbench import run
    from repro.core import spans
    path = str(FIXTURE / "scoped.xplane.pb.gz")
    tr = trace.read_xplane(path, run.SPANS)
    saved = json.loads((FIXTURE / "scoped.json").read_text())
    rec = spans.Record(spans=[spans.Span(*s) for s in saved["spans"]],
                       counters=saved["counters"],
                       n_requests=sum(s[3] < 0 for s in saved["spans"]))
    return tr, pt.read_program(path), rec, saved["scopes"]


def test_recorded_spans_are_on_the_trace_clock():
    """After one offset, every recorded span starts and ends within 50 us
    of its own annotation in the trace."""
    _, ptr, rec, _ = scoped_fixture()
    mine = [(s.name, s.start_ns, s.end_ns) for s in rec.spans
            if s.name.startswith(pt.SPAN_PREFIX)]
    pairs = pt.match_spans(mine, ptr.spans)
    assert len(pairs) == len(mine) == len(ptr.spans) > 20
    assert all(m[0] == t[0] for m, t in pairs)
    n, _, worst = pt.clock_error_ns(mine, ptr.spans)
    assert n == len(mine) and worst < 50_000


def test_recorded_trace_idle_by_span_and_scan_phases():
    tr, ptr, _, scopes = scoped_fixture()
    red = trace.reduce(tr)
    assert red.n_requests == 2
    by_span = pt.idle_by_span(pt.window_holes(tr), ptr.spans)
    assert sum(by_span.values()) == pytest.approx(red.window_s - red.busy_s)
    assert by_span.get(pt.OTHER, 0.0) < 0.1 * sum(by_span.values())
    assert {"streamsim.dispatch", "streamsim.fetch",
            "streamsim.build_static"} <= set(by_span)
    phases = pt.scan_phases(tr, ptr.modules, scopes)
    assert set(phases) <= set(pt.SCOPES) | {pt.UNSCOPED}
    assert {"publish_gate", "pump_assign", "transit",
            "consume_ack"} <= set(phases)
    total = sum(phases.values())
    assert phases.get(pt.UNSCOPED, 0.0) < 0.1 * total
    assert phases["transit"] > 0.5 * total
    # leaf ops only: the loops that hold them are not counted again
    assert total < red.busy_s
