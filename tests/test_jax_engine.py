"""Jit-boundary unit tests for the JAX engine (``engine="jax"``):

* ``_fifo_scan`` NumPy-vs-JAX elementwise equality on hypothesis
  inputs, solo and lane-stacked — the scan is the same float64 closed
  form (cumsum + running max), so the two engines may differ only by
  re-association noise;
* the **pad-and-mask contract**: pow2 padding with inert values (+inf
  arrivals, zero holds, consumed depart rows) never perturbs a real
  lane — at the kernel level and for whole stacked runs (adding a
  seed-lane leaves the existing lanes bit-identical);
* **scoped x64**: engine kernels compute in float64 with full
  time-arithmetic resolution (a 1e-4 s hold survives a 1e3 s clock)
  while the process-global JAX default stays x32 for the model stack;
* ``run_many``'s per-cell fallback when jax is unavailable, recorded
  on the result (``Summary.engine``).
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import jax_engine
from repro.core.jax_engine import _pow2, jax_available, jax_supported
from repro.core.metrics import summarize
from repro.core.simulator import ExperimentSpec, SimParams
from repro.core.vectorized import _fifo_scan, run_many
from repro.core.workloads import get_workload

requires_jax = pytest.mark.skipif(not jax_available(),
                                  reason="jax not installed")


def _spec(seed, engine="jax", msgs=256, nc=2):
    return ExperimentSpec(
        pattern="feedback", workload=get_workload("dstream"), arch="dts",
        n_producers=nc, n_consumers=nc, total_messages=msgs,
        params=SimParams(seed=seed, engine=engine))


# -- shape bucketing --------------------------------------------------------


def test_pow2_buckets():
    assert [_pow2(n) for n in (0, 1, 2, 3, 4, 5, 17, 64)] == \
        [1, 1, 2, 4, 4, 8, 32, 64]


# -- _fifo_scan: numpy vs jax elementwise ----------------------------------


@requires_jax
@settings(max_examples=40, deadline=None)   # first call per pow2 bucket compiles
@given(holds=st.lists(st.floats(min_value=0.0, max_value=5.0),
                      min_size=1, max_size=33),
       gaps=st.lists(st.floats(min_value=0.0, max_value=3.0),
                     min_size=1, max_size=33),
       carry=st.floats(min_value=0.0, max_value=20.0))
def test_jax_fifo_scan_matches_numpy_1d(holds, gaps, carry):
    """Sizes 1..33 sweep across pow2 pad boundaries, so this is also
    the kernel-level pad-and-mask invariance check."""
    n = min(len(holds), len(gaps))
    a = np.cumsum(np.asarray(gaps[:n]))
    h = np.asarray(holds[:n])
    got = jax_engine._jax_fifo_scan(a, h, carry)
    want = _fifo_scan(a, h, carry)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)


@requires_jax
@settings(max_examples=25, deadline=None)   # first call per pow2 bucket compiles
@given(holds=st.lists(st.floats(min_value=0.0, max_value=5.0),
                      min_size=1, max_size=20),
       gaps=st.lists(st.floats(min_value=0.0, max_value=3.0),
                     min_size=1, max_size=20),
       scales=st.lists(st.floats(min_value=0.5, max_value=2.0),
                       min_size=2, max_size=5),
       carry=st.floats(min_value=0.0, max_value=10.0))
def test_jax_fifo_scan_matches_numpy_lane_axis(holds, gaps, scales, carry):
    n = min(len(holds), len(gaps))
    sc = np.asarray(scales)
    a = np.cumsum(np.asarray(gaps[:n]))[:, None] * sc[None, :]
    h = np.asarray(holds[:n])[:, None] * sc[None, :]
    got = jax_engine._jax_fifo_scan(a, h, carry * sc)
    want = _fifo_scan(a, h, carry * sc)
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)


@requires_jax
def test_jax_fifo_scan_broadcasts_scalar_hold_and_carry():
    a = np.array([[0.0, 0.0], [1.0, 2.0], [1.5, 4.0]])
    got = jax_engine._jax_fifo_scan(a, 0.5, 0.0)
    want = _fifo_scan(a, np.full_like(a, 0.5), np.zeros(2))
    np.testing.assert_allclose(got, want, rtol=1e-12)


# -- pad-and-mask invariance -----------------------------------------------


@requires_jax
def test_kernel_pads_are_inert():
    """Explicitly widening a kernel call with its documented pad values
    leaves the real prefix bit-identical."""
    K = jax_engine._kernels()
    rng = np.random.default_rng(1)
    a = np.sort(rng.uniform(0, 10, (8, 3)), axis=0)
    h = rng.uniform(0, 1e-3, (8, 3))
    c = np.zeros(3)
    base = np.asarray(K.fifo_scan_lanes(a, h, c))
    ap = np.vstack([a, np.full((8, 3), np.inf)])
    hp = np.vstack([h, np.zeros((8, 3))])
    wide = np.asarray(K.fifo_scan_lanes(ap, hp, c))[:8]
    assert np.array_equal(base, wide)
    # masked depart pops: consumed +inf pad rows never count
    t = np.array([1.0, 3.0, 5.0, np.inf])
    used = np.array([False, False, False, True])
    cnt, last, used2 = K.pop_until(t, used, 4.0)
    assert int(cnt) == 2 and float(last) == 3.0
    assert np.asarray(used2).tolist() == [True, True, False, True]
    t2 = np.concatenate([t, np.full(4, np.inf)])
    u2 = np.concatenate([used, np.ones(4, dtype=bool)])
    cnt2, last2, _ = K.pop_until(t2, u2, 4.0)
    assert int(cnt2) == 2 and float(last2) == 3.0
    assert float(K.next_drain(t, used)) == 1.0


@requires_jax
def test_added_seed_lane_never_perturbs_existing_lanes():
    """Whole-run pad-and-mask invariance: stacking one more seed-lane
    leaves the existing lanes' trajectories bit-identical (overflow
    regime included, so the masked depart store and the admission scan
    both face real flow-control traffic)."""
    from repro.core.patterns import OVERFLOW_STRESS_DEFAULTS
    wl = get_workload("dstream")
    spec = ExperimentSpec(
        pattern="feedback", workload=wl, arch="dts", n_producers=2,
        n_consumers=2, total_messages=512,
        params=SimParams(seed=0, engine="jax",
                         queue_max_bytes=64 * wl.payload_bytes,
                         **OVERFLOW_STRESS_DEFAULTS))
    two = jax_engine.JaxStreamSim(spec, stack_seeds=[0, 7]).run_stacked()
    three = jax_engine.JaxStreamSim(
        spec, stack_seeds=[0, 7, 99]).run_stacked()
    for i in range(2):
        assert np.array_equal(two[i].consume_times,
                              three[i].consume_times), i
        assert two[i].rejected_publishes == three[i].rejected_publishes
        assert two[i].blocked_confirms == three[i].blocked_confirms


# -- scoped x64 -------------------------------------------------------------


@requires_jax
def test_x64_time_arithmetic_roundtrip_without_global_flip():
    import jax
    import jax.numpy as jnp
    global_x64 = jax.config.jax_enable_x64
    # large-magnitude clocks: a 1e-4 s hold on a 1e3 s base survives
    # only in float64 (f32 resolution at 1e3 is ~6e-5 and accumulates)
    a = 1e3 + np.cumsum(np.full(32, 1e-4))
    h = np.full(32, 1e-4)
    got = jax_engine._jax_fifo_scan(a, h, 1e3)
    assert got.dtype == np.float64
    np.testing.assert_allclose(got, _fifo_scan(a, h, 1e3), rtol=0,
                               atol=1e-12)
    assert np.all(np.diff(got) > 0)          # holds never vanish
    # the engine's x64 is scoped per call: the process-global default
    # (the model/kernel stack's x32) is untouched
    assert jax.config.jax_enable_x64 == global_x64
    if not global_x64:
        assert jnp.asarray(1.0).dtype == jnp.float32


# -- engine selection, fallback recording ----------------------------------


@requires_jax
def test_jax_engine_runs_and_records_engine():
    rs = run_many([_spec(0), _spec(1)])
    for r, seed in zip(rs, (0, 1)):
        assert r.feasible and r.n_consumed == 256
        s = summarize(r)
        assert s.engine == "jax", seed


def test_run_many_falls_back_and_records_vectorized(monkeypatch):
    """Without importable jax, run_many reroutes jax cells to the
    vectorized engine and the results say so."""
    monkeypatch.setattr(jax_engine, "jax_available", lambda: False)
    ok, why = jax_supported(_spec(0))
    assert not ok and "jax" in why
    rs = run_many([_spec(0)])
    assert rs[0].feasible
    assert rs[0].spec.params.engine == "vectorized"
    assert summarize(rs[0]).engine == "vectorized"
    assert summarize(rs[0]).path == "vectorized"


@requires_jax
def test_jax_matches_vectorized_bitwise_on_smoke_cell():
    """The jax engine is a kernel-layer port of the same arithmetic:
    on a smoke cell the two engines agree to the last bit."""
    j = run_many([_spec(0, "jax")])[0]
    v = run_many([_spec(0, "vectorized")])[0]
    np.testing.assert_allclose(j.consume_times, v.consume_times,
                               rtol=1e-9)
    np.testing.assert_allclose(j.rtts, v.rtts, rtol=1e-9)
    assert j.rejected_publishes == v.rejected_publishes
    assert j.blocked_confirms == v.blocked_confirms


# -- whole-run device loop --------------------------------------------------
# The wave device program (repro.core.jax_device_loop): one lax.scan
# over message generations replaces the per-cohort Python event loop.
# Contracts under test: the jit program computes exactly what its
# NumPy-mirror step loop computes; the pow2 cell-axis padding is inert;
# lane 0 of a stacked run is bit-identical to the solo device run; and
# end-to-end throughput/RTT stay inside the device_loop.* parity bands
# vs the vectorized engine.


def _dl_spec(seed, pattern="feedback", arch="dts", msgs=None, npr=None,
             nc=None, engine="jax", device=True, **ov):
    # feedback: 32 x 32 with 32 msgs/producer under a confirm window of
    # 128 keeps the default cell in the regime the device program takes
    # (a square fleet, the window never binds; see _device_loop_ok), so
    # the dispatch-path tests exercise the device program rather than
    # silently falling back to the cohort loop; work sharing keeps its
    # 4 x 2 cell of 256 msgs under a window of 32, which binds
    fb = pattern == "feedback"
    npr = npr or (32 if fb else 4)
    nc = nc or (npr if fb else 2)
    msgs = msgs or (32 * npr if fb else 256)
    ov.setdefault("confirm_window", 128 if fb else 32)
    return ExperimentSpec(
        pattern=pattern, workload=get_workload("dstream"), arch=arch,
        n_producers=npr, n_consumers=nc, total_messages=msgs,
        params=SimParams(seed=seed, engine=engine,
                         jax_device_loop=device, **ov))


def _dl_sim(seed, **kw):
    from repro.core.vectorized import VectorizedStreamSim
    kw.setdefault("engine", "vectorized")
    kw.setdefault("device", None)
    return VectorizedStreamSim(_dl_spec(seed, **kw))


@requires_jax
@pytest.mark.parametrize("pattern", [
    # the feedback trace carries the pending-reply buffer — jit-compile
    # heavy, so it rides the nightly/jax-engine jobs only
    pytest.param("feedback", marks=pytest.mark.slow),
    "work_sharing"])
def test_device_loop_trace_jax_matches_numpy_mirror(pattern):
    """The jit device program and the same step run as a Python loop
    (backend="numpy") produce identical per-step traces — any
    divergence is a jit/vmap artifact, never modeling noise."""
    from repro.core import jax_device_loop as dl
    # feedback: the default 32 x 32 cell, 32 msgs/producer under a
    # window of 128 (replies held back and drained); the work_sharing
    # trace stays tiny for compile time
    sim = _dl_sim(0, pattern=pattern, jitter=0.02,
                  msgs=None if pattern == "feedback" else 64)
    ok, why = dl._device_loop_ok(sim)
    assert ok, why
    ws = dl.build_static(sim)
    jit = dl.draw_jitter(sim, ws)
    yn = dl.run_wave_trace(ws, jit, backend="numpy")
    yj = dl.run_wave_trace(ws, jit, backend="jax")
    assert set(yn) == set(yj)
    for k in yn:
        np.testing.assert_allclose(yj[k], yn[k], rtol=1e-12, atol=1e-12,
                                   err_msg=k)


@requires_jax
@pytest.mark.slow
def test_device_loop_cell_axis_pads_are_inert():
    """run_wave_cells runs the first cell alone and pads the 3-cell
    group after it to 4 by replicating its cell 0; every real cell's
    results are bit-identical to its solo device run."""
    from repro.core import jax_device_loop as dl
    from repro.core import spans
    seeds = (0, 1, 2, 3)
    with spans.recording() as rec:
        batched = dl.run_wave_cells(
            [_dl_sim(s, msgs=64, npr=4, nc=2, jitter=0.02) for s in seeds])
    m = dl.build_static(_dl_sim(0, msgs=64, npr=4, nc=2)).meta
    assert rec.counters["streamsim.programs"] == 2
    assert rec.counters["streamsim.cells"] == 4
    # the first cell alone, then three cells padded to four
    assert rec.counters["streamsim.padded_member_steps"] == (
        5 * m["Np"] * m["L"] * m["nSteps"])
    for s, rs in zip(seeds, batched):
        solo = dl.run_wave_results(
            _dl_sim(s, msgs=64, npr=4, nc=2, jitter=0.02))
        assert len(rs) == len(solo) == 1
        np.testing.assert_array_equal(rs[0].consume_times,
                                      solo[0].consume_times)
        np.testing.assert_array_equal(rs[0].rtts, solo[0].rtts)


@requires_jax
@pytest.mark.parametrize("cells,via,counts", [
    pytest.param((("dts", 0), ("prs-haproxy", 0), ("mss", 0)),
                 "run_wave_cells", (3, 3, 2), id="three-signatures"),
    pytest.param((("dts", 1), ("mss", 0), ("mss", 2)),
                 "run_wave_cells", (2, 3, 2),
                 id="two-cell-group-and-a-singleton"),
    pytest.param((("dts", 2), ("mss", 0), ("mss", 1), ("mss", 3)),
                 "run_wave_cells", (2, 4, 3),
                 id="padded-three-cell-group-and-a-singleton"),
    # after the lone first cell, the mss group (cells 1 and 3) runs
    # before cell 2: programs run out of input order
    pytest.param((("dts", 0), ("mss", 0), ("prs-haproxy", 0), ("mss", 1)),
                 "run_wave_cells", (3, 4, 3),
                 id="interleaved-group-after-a-lone-first"),
    pytest.param((("dts", 0), ("prs-haproxy", 0), ("mss", 0)),
                 "run_many", (3, 3, 2), id="run-many-three-architectures"),
    # as above through run_many; the second mss group differs in jitter
    # alone, so it stacks apart and fuses with the first by signature
    pytest.param((("dts", 0), ("mss", 0), ("prs-haproxy", 0),
                  ("mss", 1, 0.03)),
                 "run_many", (3, 4, 3), id="run-many-interleaved-group"),
    # prs-haproxy and prs-haproxy-c4 build one signature; the third
    # group differs in jitter alone, so it does not stack with the first
    pytest.param((("prs-haproxy", 0), ("prs-haproxy-c4", 0),
                  ("prs-haproxy", 1, 0.03)),
                 "run_many", (2, 3, 2), id="run-many-first-signature-shared"),
    pytest.param((("mss", 0),), "run_many", (1, 1, 0),
                 id="run-many-one-cell")])
def test_device_loop_pipelined_cells_match_solo_runs(cells, via, counts):
    """run_wave_cells enqueues the first cell's program before it builds
    the rest, then each signature group's program before it waits on
    the one before and assembles it; every sim's lanes are
    bit-identical to the sim's own run_wave_results, in input order.
    ``counts`` are the programs, cells and static builds that start
    while a program is enqueued and not yet waited on."""
    from repro.core import jax_device_loop as dl
    from repro.core import spans
    from repro.core.jax_engine import JaxStreamSim
    from repro.core.vectorized import VectorizedStreamSim

    engine = "jax" if via == "run_many" else "vectorized"
    cls = JaxStreamSim if via == "run_many" else VectorizedStreamSim

    def spec(a, s, jitter=0.02):
        return _dl_spec(s, pattern="work_sharing", arch=a, msgs=64,
                        engine=engine,
                        device=True if via == "run_many" else None,
                        jitter=jitter)

    def sims():
        return [cls(spec(*c), None, stack_seeds=[c[1], c[1] + 100])
                for c in cells]
    _, *rest = [dl.build_static(sim).signature() for sim in sims()]
    with spans.recording() as rec:
        if via == "run_many":
            flat = run_many([spec(a, seed, *j) for a, s, *j in cells
                             for seed in (s, s + 100)])
            batched = [flat[k:k + 2] for k in range(0, len(flat), 2)]
        else:
            batched = dl.run_wave_cells(sims())
    c = rec.counters
    assert (c["streamsim.programs"], c["streamsim.cells"],
            c["streamsim.builds_ahead"]) == counts
    # the first cell runs alone; the rest fuse by signature
    assert counts[0] == 1 + len(set(rest))
    assert len(batched) == len(cells)
    for got, sim in zip(batched, sims()):
        want = dl.run_wave_results(sim)
        assert len(got) == len(want) == 2
        for a, b in zip(got, want):
            assert a.spec == b.spec and a.path == b.path == "jax-device"
            for f in ("consume_times", "rtts", "publish_starts"):
                np.testing.assert_array_equal(getattr(a, f), getattr(b, f),
                                              err_msg=f)


@requires_jax
@pytest.mark.slow
def test_device_loop_stacked_pilot_bit_identical():
    """Lane 0 of a seed-stacked device run equals the solo device run
    bit-for-bit (each lane draws jitter from its own seed stream)."""
    stacked = run_many([_dl_spec(s, jitter=0.02)
                        for s in (0, 1000, 2000)])
    solo = run_many([_dl_spec(0, jitter=0.02)])[0]
    assert all(summarize(r).engine == "jax" for r in stacked)
    np.testing.assert_array_equal(stacked[0].consume_times,
                                  solo.consume_times)
    np.testing.assert_array_equal(stacked[0].rtts, solo.rtts)


#: the paper's Fig 6 points (work sharing with feedback, Dstream, 3072
#: messages, producers = consumers) the device program takes: the
#: confirm window of 128 never binds at 96 and 48 msgs/producer.  At
#: c64, mss reads -5.9% throughput against the vectorized engine (seed
#: 0; -1.2% to -6.0% over seeds 0-7), whose own throughput there sits
#: 3.6% to 8.8% above the heap engine's; the device program's, 2.1% to
#: 2.3% above, with its median RTT within 0.1% of the heap's
FIG6 = [pytest.param("feedback", a, nc, 3072, id=f"fig6-{a}-c{nc}")
        for nc in (32, 64) for a in ("dts", "prs-haproxy", "mss")]


def _mirror_results(spec):
    """The device program's results, its step run by the NumPy mirror
    (the same step function; ``test_device_loop_trace_jax_matches_numpy_
    mirror`` holds the jit program to it) — no compile per shape."""
    from repro.core import jax_device_loop as dl
    from repro.core.vectorized import VectorizedStreamSim
    sim = VectorizedStreamSim(spec)
    ws = dl.build_static(sim)
    ys = dl.run_wave_trace(ws, dl.draw_jitter(sim, ws), backend="numpy")
    return dl._assemble(sim, ws, ys)


@requires_jax
@pytest.mark.slow
@pytest.mark.parametrize("pattern,arch,nc,msgs", [
    # the default cells: feedback 32 x 32 with 32 msgs/producer, work
    # sharing 4 x 2 with 256 msgs (the feedback shapes of the former
    # corridor are declined; see test_device_loop_regime_gate)
    pytest.param("feedback", "dts", None, None, id="feedback-dts"),
    pytest.param("work_sharing", "prs-haproxy", None, None,
                 id="work_sharing-prs-haproxy"),
    pytest.param("work_sharing", "dts", None, None, id="work_sharing-dts"),
    pytest.param("work_sharing", "mss", None, None, id="work_sharing-mss")]
    + FIG6)
def test_device_loop_parity_vs_vectorized(pattern, arch, nc, msgs):
    """End-to-end parity of the whole-run device program against the
    vectorized cohort loop, inside the device_loop.* bands."""
    from repro.core import jax_device_loop as dl
    from repro.core.parity import band
    kw = dict(pattern=pattern, arch=arch, npr=nc, nc=nc, msgs=msgs)
    ok, why = dl._device_loop_ok(_dl_sim(0, **kw))
    assert ok, f"cell unexpectedly regime-gated: {why}"
    v = run_many([_dl_spec(0, engine="vectorized", device=None, **kw)])[0]
    if msgs is None:
        j = run_many([_dl_spec(0, **kw)])[0]
        assert summarize(j).engine == "jax"
    else:
        (j,) = _mirror_results(_dl_spec(0, **kw))
    sv, sj = summarize(v), summarize(j)
    thr_dev = (abs(sj.throughput_msgs_s - sv.throughput_msgs_s)
               / sv.throughput_msgs_s)
    assert thr_dev <= band("device_loop.all.throughput"), (
        f"{pattern}/{arch}: thr dev {thr_dev:.4f}")
    if pattern == "feedback":
        rv, rj = np.median(v.rtts), np.median(j.rtts)
        rtt_dev = abs(rj - rv) / rv
        assert rtt_dev <= band("device_loop.all.median_rtt"), (
            f"{pattern}/{arch}: rtt dev {rtt_dev:.4f}")


@requires_jax
@pytest.mark.parametrize("arch,nc,msgs,device", [
    ("dts", 32, 1024, True), ("prs-haproxy", 32, 1024, True),
    ("prs-haproxy", 64, 3072, False), ("dts", 32, 3072, False),
    ("mss", 64, 3072, False)])
def test_device_loop_receives_every_reply(arch, nc, msgs, device):
    """Every consumed message's reply comes back before the scan ends:
    no reply is left in the pending-reply buffer, which would read as a
    non-finite RTT.  The 64-consumer cell holds almost every reply back
    until the publishes are done, so its drain steps do the most."""
    from repro.core import jax_device_loop as dl
    npr = nc
    spec = _dl_spec(0, arch=arch, npr=npr, nc=nc, msgs=msgs, jitter=0.03)
    results = (run_many([spec, _dl_spec(1, arch=arch, npr=npr, nc=nc,
                                        msgs=msgs, jitter=0.03)])
               if device else _mirror_results(spec))
    for r in results:
        assert r.rtts.size == msgs and np.isfinite(r.rtts).all()
        assert (r.rtts > 0).all()
        assert np.array_equal(np.bincount(r.rtt_producers),
                              np.full(npr, msgs // npr))
        if device:
            assert r.path == "jax-device"
    ws = dl.build_static(_dl_sim(0, arch=arch, npr=npr, nc=nc, msgs=msgs))
    m = ws.meta
    assert m["nSteps"] == m["nGen"] + 1 + m["drain"]
    assert m["C"] == max(msgs, m["Np"])


@requires_jax
def test_device_loop_dispatch_requires_opt_in():
    """jax_device_loop=None (the default) keeps the cohort-loop jax
    engine; only the explicit True flag dispatches the wave program."""
    from repro.core import jax_device_loop as dl
    sim = _dl_sim(0)
    ok, why = dl._device_loop_ok(sim)
    assert ok, why
    j_default = run_many([_dl_spec(0, device=None)])[0]
    v = run_many([_dl_spec(0, engine="vectorized", device=None)])[0]
    # the cohort jax engine is a kernel port: bitwise-close to
    # vectorized, which the wave program (different schedule) is not
    np.testing.assert_allclose(j_default.consume_times,
                               v.consume_times, rtol=1e-9)


@requires_jax
def test_recorded_path_tells_device_program_from_cohort_path():
    """``RunResult.path`` / ``Summary.path`` name the path that ran, and
    the campaign cache key carries it, so the device program and the
    per-cohort jax path never share a cached row."""
    from repro.core.campaign import resolved_path
    dev, coh = (_dl_spec(0, pattern="work_sharing", msgs=64, device=d)
                for d in (True, None))
    assert resolved_path(dev) == "jax-device"
    assert resolved_path(coh) == "jax-cohort"
    rd, rc = run_many([dev])[0], run_many([coh])[0]
    assert (rd.path, summarize(rd).path) == ("jax-device", "jax-device")
    assert (rc.path, summarize(rc).path) == ("jax-cohort", "jax-cohort")
    assert summarize(rd).engine == summarize(rc).engine == "jax"
    # a device request the regime gate declines runs, and says, cohort
    gated = _dl_spec(0, pattern="broadcast_gather", msgs=64, npr=1)
    assert resolved_path(gated) == "jax-cohort"
    assert run_many([gated])[0].path == "jax-cohort"


def test_device_loop_regime_gate():
    """The regime gate rejects every shape class whose wave schedule
    measurably diverges from the cohort loop, each with a reason naming
    the offending quantity (gated cells dispatch to the per-cohort
    path; see test_device_loop_dispatch_requires_opt_in for the
    dispatch side)."""
    from repro.core import jax_device_loop as dl

    def why_of(**kw):
        ok, why = dl._device_loop_ok(_dl_sim(0, **kw))
        assert not ok
        return why

    # the shapes the former feedback corridor was drawn around, all on
    # 4 producers: a binding window (its own W=32, the hard stall W=16,
    # a run of 8 windows) meets the binding-window clause; the burst
    # shape (W=128 over 64 msgs/producer) and mss, the fleet clause
    for msgs, w in ((256, 32), (256, 16), (1024, 32)):
        assert "binding confirm window" in why_of(npr=4, nc=2, msgs=msgs,
                                                  confirm_window=w)
    for arch in ("dts", "mss"):
        assert "4 x 2 fleet" in why_of(npr=4, nc=2, msgs=256, arch=arch)
    # fine generations at 16x16 (2048 msgs, W=64; +11% throughput) and
    # Fig 6 at 16 consumers (192 msgs/producer over a window of 128)
    assert "binding confirm window" in why_of(npr=16, nc=16, msgs=2048,
                                              confirm_window=64)
    assert "binding confirm window" in why_of(npr=16, nc=16, msgs=3072)
    # a square fleet below 32 or above 96, and one with fewer consumers
    # than producers, with the window never binding
    assert "16 x 16 fleet" in why_of(npr=16, nc=16, msgs=1024)
    assert "128 x 128 fleet" in why_of(npr=128, nc=128, msgs=128 * 48)
    assert "48 x 8 fleet" in why_of(npr=48, nc=8, msgs=48 * 48)
    # a run too short for the reply schedule
    assert "16 msgs/producer < 32" in why_of(npr=64, nc=64, msgs=1024)
    # the device program's regime: Fig 6 at 32 and 64 consumers on every
    # architecture, and the default 32 x 32 cell
    for kw in [dict(npr=n, nc=n, msgs=3072, arch=a) for n in (32, 64)
               for a in ("dts", "prs-haproxy", "mss")] + [dict()]:
        ok, why = dl._device_loop_ok(_dl_sim(0, **kw))
        assert ok, why
    # universal run-length clause (any pattern): generation-barrier
    # drift accumulates past 256 msgs/producer
    assert "generation-barrier drift" in why_of(
        pattern="work_sharing", npr=8, nc=8, msgs=4096)
    # work_sharing carries only the run-length gate, no feedback gates
    ok, why = dl._device_loop_ok(
        _dl_sim(0, pattern="work_sharing", npr=16, nc=16, msgs=2048))
    assert ok, why
