"""The span-and-counter recorder (``repro.core.spans``) and the request
path it instruments: off by default at the cost of a null context, on
inside ``recording()``, and never a change to a result."""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from repro.core import jax_device_loop as dl
from repro.core import spans
from repro.core.jax_engine import JaxStreamSim
from repro.core.metrics import summarize
from repro.core.simulator import ExperimentSpec, SimParams
from repro.core.vectorized import VectorizedStreamSim, run_many
from repro.core.workloads import get_workload

ARCHS = ("dts", "prs-haproxy", "mss")
SEEDS = (1, 2)


def _spec(arch: str, seed: int, jitter: float = 0.1) -> ExperimentSpec:
    return ExperimentSpec(
        pattern="work_sharing", workload=get_workload("dstream"), arch=arch,
        n_producers=2, n_consumers=2, total_messages=32,
        params=SimParams(seed=seed, engine="jax", jax_device_loop=True,
                         jitter=jitter))


def _specs() -> list:
    return [_spec(a, s) for a in ARCHS for s in SEEDS]


def _sims() -> list:
    return [JaxStreamSim(_spec(a, SEEDS[0]), None, stack_seeds=list(SEEDS))
            for a in ARCHS]


def _request(specs: list) -> list:
    with spans.span("request"):
        results = run_many(specs)
        for r in results:
            summarize(r)
    return results


@pytest.fixture(scope="module")
def recorded():
    """One untimed request (compiles), then one recorded."""
    off = run_many(_specs())
    with spans.recording() as rec:
        on = _request(_specs())
    return off, on, rec


def test_off_by_default_is_one_null_context():
    assert not spans.on()
    a, b = spans.span("x"), spans.span("y")
    assert a is b
    with a:
        spans.count("streamsim.engines", 5)
    with spans.recording() as rec:
        pass
    assert rec.spans == [] and rec.counters == {} and rec.n_requests == 0
    assert not spans.on()


def test_recording_does_not_nest():
    with spans.recording():
        with pytest.raises(RuntimeError):
            with spans.recording():
                pass


def test_roots_parents_and_self_time():
    with spans.recording() as rec:
        with spans.span("a"):
            with spans.span("b"):
                spans.count("n", 2)
            with spans.span("c"):
                pass
        with spans.span("a"):
            spans.count("n", 3)
    assert [(s.name, s.parent, s.request) for s in rec.spans] == [
        ("a", -1, 0), ("b", 0, 0), ("c", 0, 0), ("a", -1, 1)]
    assert rec.n_requests == 2 and rec.counters == {"n": 5}
    assert all(s.end_ns >= s.start_ns for s in rec.spans)
    d = {s.name: s.end_ns - s.start_ns for s in rec.spans[:3]}
    per = rec.requests()
    assert per[0]["a"] == d["a"] - d["b"] - d["c"]
    assert per[1] == {"a": rec.spans[3].end_ns - rec.spans[3].start_ns}
    assert rec.self_ns()["a"] == per[0]["a"] + per[1]["a"]


def test_counts_are_kept_per_request():
    """A count made under a span adds to its request's counters too; one
    made with no span open adds to the totals only."""
    with spans.recording() as rec:
        with spans.span("a"):
            with spans.span("b"):
                spans.count("n", 2)
        spans.count("n", 7)
        with spans.span("a"):
            spans.count("n", 3)
            spans.count("m", 1)
    assert rec.counters == {"n": 12, "m": 1}
    assert rec.request_counters == {0: {"n": 2}, 1: {"n": 3, "m": 1}}


def test_span_tree_of_a_request(recorded):
    _, _, rec = recorded
    spans_ = rec.spans
    assert rec.n_requests == 1
    assert {s.request for s in spans_} == {0}
    parent = {i: (spans_[s.parent].name if s.parent >= 0 else None)
              for i, s in enumerate(spans_)}
    names = [s.name for s in spans_]
    want_parent = {
        "request": None, "streamsim.run_many": "request",
        "streamsim.summarize": "request",
        "streamsim.engine": "streamsim.run_many",
        "streamsim.build_static": "streamsim.run_many",
        "streamsim.draw_jitter": "streamsim.run_many",
        "streamsim.program": "streamsim.run_many",
        "streamsim.assemble": "streamsim.run_many",
        "streamsim.dispatch": "streamsim.program",
        "streamsim.wait": "streamsim.assemble",
        "streamsim.fetch": "streamsim.assemble"}
    assert set(names) == set(want_parent)
    for i, n in enumerate(names):
        assert parent[i] == want_parent[n], n
    # the first architecture's program is enqueued before the next
    # engines (probe and stacked) are constructed and built; then one
    # program ahead: each is enqueued before the one before it is
    # waited on and assembled
    top = [n for i, n in enumerate(names)
           if parent[i] == "streamsim.run_many"]
    eng, build, draw, prog, asm = (
        "streamsim.engine", "streamsim.build_static",
        "streamsim.draw_jitter", "streamsim.program", "streamsim.assemble")
    assert top == [eng, eng, build, draw, prog,
                   eng, eng, build, eng, eng, build,
                   draw, prog, asm, draw, prog, asm, asm]
    # a program span is its dispatch; its assembly waits on it, then
    # copies back the two outputs a work-sharing result reads
    for i, n in enumerate(names):
        kids = [names[j] for j in range(len(spans_)) if spans_[j].parent == i]
        if n == prog:
            assert kids == ["streamsim.dispatch"]
        if n == asm:
            assert kids == ["streamsim.wait", "streamsim.fetch",
                            "streamsim.fetch"]
    assert names.count("streamsim.summarize") == len(ARCHS) * len(SEEDS)


def test_counters_match_what_was_built(recorded):
    _, _, rec = recorded
    c = rec.counters
    groups = len(ARCHS)      # the seeds of one architecture stack
    assert c["streamsim.engines"] == 2 * groups
    # the architectures' programs differ in signature: one cell each
    assert c["streamsim.programs"] == groups
    assert c["streamsim.cells"] == groups
    assert sum(s.name == "streamsim.engine" for s in rec.spans) == 2 * groups
    sims = _sims()
    wss = [dl.build_static(sim) for sim in sims]
    assert len({ws.signature() for ws in wss}) == groups
    assert c["streamsim.member_steps"] == sum(
        int(ws.sizes["valid"].sum()) * ws.meta["L"] for ws in wss)
    assert c["streamsim.padded_member_steps"] == sum(
        ws.meta["Np"] * ws.meta["L"] * ws.meta["nSteps"] for ws in wss)
    assert 0 < c["streamsim.member_steps"] < c["streamsim.padded_member_steps"]
    assert c["streamsim.h2d_bytes"] == sum(
        a.nbytes for ws in wss for d in (ws.xs, ws.inv) for a in d.values()
    ) + sum(a.nbytes for ws, sim in zip(wss, sims)
            for a in dl.draw_jitter(sim, ws).values())
    # work sharing: assembly reads ``seen`` and ``pub_start``, each
    # (nSteps, Np, L) float64
    assert c["streamsim.d2h_bytes"] == sum(
        2 * 8 * ws.meta["nSteps"] * ws.meta["Np"] * ws.meta["L"]
        for ws in wss)
    assert c["streamsim.programs_ahead"] == groups - 1
    # every build after the first starts under the first program
    assert c["streamsim.builds_ahead"] == groups - 1
    # every program was already compiled by the unrecorded request
    assert c.get("streamsim.program_builds", 0) == 0


def test_request_counters_of_a_request(recorded):
    _, _, rec = recorded
    assert rec.request_counters == {0: rec.counters}


def test_cells_of_one_signature_share_a_program():
    """Three stack groups that differ only in jitter build one
    signature.  The first runs alone, enqueued before the other two are
    built; those two run as one program over two cells."""
    specs = [_spec("mss", s, jitter=j) for j in (0.1, 0.2, 0.3)
             for s in SEEDS]
    run_many(specs)
    with spans.recording() as rec:
        run_many(specs)
    c = rec.counters
    assert c["streamsim.programs"] == 2 and c["streamsim.cells"] == 3
    # the second program goes ahead of the first's wait, and both
    # later builds start while the first program is enqueued
    assert c["streamsim.programs_ahead"] == 1
    assert c["streamsim.builds_ahead"] == 2
    assert c["streamsim.engines"] == 6
    ws = dl.build_static(JaxStreamSim(specs[0], None,
                                      stack_seeds=list(SEEDS)))
    # one cell alone, then two cells (a pow2: no pad)
    assert c["streamsim.padded_member_steps"] == (
        3 * ws.meta["Np"] * ws.meta["L"] * ws.meta["nSteps"])
    assert c["streamsim.member_steps"] == (
        3 * int(ws.sizes["valid"].sum()) * ws.meta["L"])


def _feedback_spec(seed: int) -> ExperimentSpec:
    # a feedback cell the device program takes: a square fleet of 32,
    # 32 msgs/producer under a confirm window that never binds (see
    # _device_loop_ok)
    return ExperimentSpec(
        pattern="feedback", workload=get_workload("dstream"), arch="dts",
        n_producers=32, n_consumers=32, total_messages=1024,
        params=SimParams(seed=seed, engine="jax", jax_device_loop=True,
                         confirm_window=128, jitter=0.02))


@pytest.mark.parametrize("pattern", ["work_sharing", "feedback"])
def test_only_what_assembly_reads_is_copied_back(pattern):
    """The results path copies back ``seen`` and ``pub_start``, and
    ``back`` (each reply's receive time) where the pattern has replies;
    never ``confirms`` or ``depart``."""
    spec = (_spec("mss", 1) if pattern == "work_sharing"
            else _feedback_spec(1))
    run_many([spec])
    with spans.recording() as rec:
        (r,) = run_many([spec])
    assert r.path == "jax-device"
    ws = dl.build_static(VectorizedStreamSim(spec))
    m = ws.meta
    read = ["seen", "pub_start"] + (["back"] if pattern == "feedback" else [])
    assert list(dl._read_keys(m)) == read
    # ``seen`` and ``pub_start`` per step; ``back`` per message; with
    # the recorder on, the pending-reply peak (one int64) too
    assert rec.counters["streamsim.d2h_bytes"] == (
        2 * 8 * m["nSteps"] * m["Np"] * m["L"]
        + (8 * spec.total_messages * m["L"] + 8
           if pattern == "feedback" else 0))
    fetches = [s for s in rec.spans if s.name == "streamsim.fetch"]
    assert len(fetches) == len(read) + (pattern == "feedback")


def test_reply_counters_of_a_feedback_program():
    """A feedback program run counts its scan and drain steps and the
    pending-reply buffer's size, and assembly its largest backlog; a
    work-sharing run counts its scan steps alone."""
    spec = _feedback_spec(2)
    run_many([spec])
    with spans.recording() as rec:
        run_many([spec])
    sim = VectorizedStreamSim(spec)
    ws = dl.build_static(sim)
    m, c = ws.meta, rec.counters
    assert c["streamsim.scan_steps"] == m["nSteps"]
    assert c["streamsim.drain_steps"] == m["drain"] > 0
    assert c["streamsim.reply_capacity"] == m["C"]
    ys = dl.run_wave_trace(ws, dl.draw_jitter(sim, ws), backend="numpy")
    assert c["streamsim.reply_backlog_peak"] == int(ys["reply_peak"]) > 0
    ws_spec = _spec("mss", 1)
    run_many([ws_spec])
    with spans.recording() as rec:
        run_many([ws_spec])
    assert rec.counters["streamsim.scan_steps"] == dl.build_static(
        VectorizedStreamSim(ws_spec)).meta["nSteps"]
    assert not {"streamsim.drain_steps", "streamsim.reply_capacity",
                "streamsim.reply_backlog_peak"} & set(rec.counters)


def test_trace_returns_every_output():
    """The step-for-step surface still copies back every output: the
    four per-step traces, and with feedback each message's reply
    receive time and the pending-reply peak."""
    sim = VectorizedStreamSim(_spec("dts", 1))
    ws = dl.build_static(sim)
    ys = dl.run_wave_trace(ws, dl.draw_jitter(sim, ws), backend="jax")
    assert set(ys) == {"pub_start", "confirms", "depart", "seen"}
    for k, a in ys.items():
        assert isinstance(a, np.ndarray), k
        assert a.shape[:2] == (ws.meta["nSteps"], ws.meta["Np"]), k
    spec = _feedback_spec(1)
    sim = VectorizedStreamSim(spec)
    ws = dl.build_static(sim)
    ys = dl.run_wave_trace(ws, dl.draw_jitter(sim, ws), backend="jax")
    assert set(ys) == {"pub_start", "confirms", "depart", "seen", "back",
                       "reply_peak"}
    assert ys["back"].shape == (spec.total_messages, ws.meta["L"])
    assert 0 < int(ys["reply_peak"]) <= ws.meta["C"]


def test_results_identical_with_recording_on_and_off(recorded):
    off, on, _ = recorded
    assert len(off) == len(on)
    for a, b in zip(off, on):
        assert a.path == b.path == "jax-device"
        for f in dataclasses.fields(a):
            x, y = getattr(a, f.name), getattr(b, f.name)
            if isinstance(x, np.ndarray):
                assert np.array_equal(x, y, equal_nan=True), f.name
            else:
                assert x == y, f.name


def test_scan_phases_are_named_in_the_program():
    """The seven phases of the scan step carry named scopes into the
    lowered program (feedback cells have all four legs and the
    pending-reply buffer)."""
    from repro.compat import scoped_x64
    spec = ExperimentSpec(
        pattern="feedback", workload=get_workload("dstream"), arch="dts",
        n_producers=32, n_consumers=32, total_messages=1024,
        params=SimParams(seed=0, confirm_window=128))
    sim = VectorizedStreamSim(spec)
    assert dl._device_loop_ok(sim)[0]
    ws = dl.build_static(sim)
    fn = dl._compiled_program(ws.signature(), dl._meta_key(ws.meta), False)
    with scoped_x64():
        text = fn.lower(ws.xs, ws.inv, dl.draw_jitter(sim, ws)).as_text(
            debug_info=True)
    for scope in ("publish_gate", "pump_assign", "transit", "consume_ack",
                  "reply_admit", "reply_pump", "reply_ack"):
        assert f'loc("{scope}/' in text, scope


def test_meta_key_is_the_sorted_items():
    ws = dl.build_static(VectorizedStreamSim(_spec("mss", 1)))
    key = dl._meta_key(ws.meta)
    assert key == tuple(sorted(ws.meta.items()))
    assert hash(key) == hash(dl._meta_key(dict(ws.meta)))
