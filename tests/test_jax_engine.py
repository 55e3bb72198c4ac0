"""Unit tests for the JAX engine (``engine="jax"``):

* the device program's **op layer** (``jax_device_loop._jax_ops``)
  against its NumPy backend (``_NumpyOps``) on hypothesis inputs, with
  the **pad-and-mask contract**'s inert pads appended (+inf clocks,
  masked rows, dummy scatter slots): the backends agree, and the pads
  never perturb a real row;
* **scoped x64**: the device ops compute in float64 with full
  time-arithmetic resolution (a 1e-4 s hold survives a 1e3 s clock)
  while the process-global JAX default stays x32 for the model stack;
* stacked seed-lanes: adding a lane leaves the existing lanes
  bit-identical;
* ``run_many``'s routing: a jax cell runs on the whole-run device
  program where the regime gate admits it, and otherwise on the
  vectorized engine, bit for bit; the fallback when jax is unavailable
  is recorded on the result (``Summary.engine``).
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import jax_engine
from repro.core.jax_engine import jax_available, jax_supported
from repro.core.metrics import summarize
from repro.core.simulator import ExperimentSpec, SimParams
from repro.core.vectorized import VectorizedStreamSim, run_many
from repro.core.workloads import get_workload

requires_jax = pytest.mark.skipif(not jax_available(),
                                  reason="jax not installed")


def _spec(seed, engine="jax", msgs=256, nc=2):
    return ExperimentSpec(
        pattern="feedback", workload=get_workload("dstream"), arch="dts",
        n_producers=nc, n_consumers=nc, total_messages=msgs,
        params=SimParams(seed=seed, engine=engine))


# -- the device program's op layer: jax vs numpy, pads inert ---------------

#: rows of inert pads appended to every op input (the member axes pad to
#: the next power of two the same way)
PADS = 3

#: no subnormals: XLA flushes them to zero, and no clock is one
_clocks = st.floats(min_value=0.0, max_value=50.0, allow_subnormal=False)


def _draw_clocks(data, n, L):
    return np.array(data.draw(st.lists(_clocks, min_size=n * L,
                                       max_size=n * L)),
                    dtype=np.float64).reshape(n, L)


def _inf_pads(x):
    return np.concatenate([x, np.full((PADS,) + x.shape[1:], np.inf)])


def _op_case(op, data):
    """``(args, padded_args, real)`` for one op: the numpy backend runs
    ``args``, both backends run ``padded_args``, and ``real(out)`` picks
    the real rows of an output on the padded input."""
    n = data.draw(st.integers(1, 12), label="n")
    L = data.draw(st.integers(1, 3), label="lanes")
    x = _draw_clocks(data, n, L)
    if op == "lexsort":
        # (idx, clock, group) as the program sorts members: a unique idx
        # key breaks every tie; masked rows carry a +inf clock
        grp = np.array(data.draw(st.lists(st.integers(0, 2), min_size=n,
                                          max_size=n)))
        pgrp = np.array(data.draw(st.lists(st.integers(0, 2),
                                           min_size=PADS, max_size=PADS)))
        keys = (np.arange(n), x[:, 0], grp)
        pkeys = (np.arange(n + PADS), _inf_pads(x)[:, 0],
                 np.concatenate([grp, pgrp]))
        return (keys,), (pkeys,), lambda o: o[o < n]
    if op in ("cumsum", "cummax"):
        pad = (np.concatenate([x, np.zeros((PADS, L))]) if op == "cumsum"
               else _inf_pads(x))
        return (x,), (pad,), lambda o: o[:n]
    if op == "seg_cummax":
        start = np.array(data.draw(st.lists(st.booleans(), min_size=n,
                                            max_size=n)))
        pstart = np.concatenate([start, np.ones(PADS, bool)])
        return (x, start), (_inf_pads(x), pstart), lambda o: o[:n]
    m = data.draw(st.integers(n, n + 4), label="slots")
    arr = _draw_clocks(data, m, L)
    # one dummy slot per pad row past the real ones, as masked members
    # scatter to a dummy chain
    parr = np.concatenate([arr, np.zeros((PADS, L))])
    if op == "at_set":
        # unique targets: jax leaves the winner of a duplicate unspecified
        idx = np.array(data.draw(st.permutations(range(m))))[:n]
        pidx = np.concatenate([idx, m + np.arange(PADS)])
    elif op == "at_max":
        idx = np.array(data.draw(st.lists(st.integers(0, m - 1),
                                          min_size=n, max_size=n)))
        pidx = np.concatenate([idx, np.full(PADS, m)])
    else:
        raise ValueError(op)
    return ((arr, idx, x), (parr, pidx, _inf_pads(x)),
            lambda o: o[:m])


def _fifo_step(ops):
    """The FIFO recurrence as a scan step; masked rows leave the server
    as they found it."""
    xp = ops.xp

    def step(free, x):
        end = xp.maximum(x["a"], free) + x["h"]
        free = xp.where(x["valid"], end, free)
        return free, {"end": end, "free": free}
    return step


@requires_jax
@pytest.mark.parametrize("op", ["lexsort", "cumsum", "cummax",
                                "seg_cummax", "at_set", "at_max", "scan"])
@settings(max_examples=25, deadline=None)   # each new shape compiles
@given(data=st.data())
def test_device_ops_match_numpy_backend_with_inert_pads(op, data):
    """Every op of the device program's jax backend computes what its
    NumPy backend computes, and inert pads appended to the input (+inf
    clocks, masked rows, dummy scatter slots) leave the real rows as the
    NumPy backend computes them without pads."""
    import jax.numpy as jnp

    from repro.compat import scoped_x64
    from repro.core import jax_device_loop as dl
    J, N = dl._jax_ops(), dl._NumpyOps
    if op == "scan":
        n = data.draw(st.integers(1, 12), label="n")
        L = data.draw(st.integers(1, 3), label="lanes")
        xs = {"a": np.sort(_draw_clocks(data, n, L), axis=0),
              "h": _draw_clocks(data, n, L) / 50.0,
              "valid": np.ones((n, 1), bool)}
        pxs = {"a": _inf_pads(xs["a"]),
               "h": np.concatenate([xs["h"], np.zeros((PADS, L))]),
               "valid": np.concatenate([xs["valid"],
                                        np.zeros((PADS, 1), bool)])}
        free = _draw_clocks(data, 1, L)[0]
        want_c, want = N.scan(_fifo_step(N), free, xs, n)
        np_c, np_ys = N.scan(_fifo_step(N), free, pxs, n + PADS)
        with scoped_x64():
            got_c, got = J.scan(_fifo_step(J), jnp.asarray(free),
                                {k: jnp.asarray(v) for k, v in pxs.items()},
                                n + PADS)
            got_c = np.asarray(got_c)
            got = {k: np.asarray(v) for k, v in got.items()}
        np.testing.assert_array_equal(got_c, np_c)
        np.testing.assert_array_equal(got_c, want_c)
        for k in want:
            assert got[k].dtype == np.float64, k
            np.testing.assert_array_equal(got[k], np_ys[k], err_msg=k)
            np.testing.assert_array_equal(got[k][:n], want[k], err_msg=k)
        return
    args, padded, real = _op_case(op, data)
    want = getattr(N, op)(*args)
    np_padded = getattr(N, op)(*padded)
    with scoped_x64():
        got = np.asarray(getattr(J, op)(*(tuple(jnp.asarray(k) for k in a)
                                          if isinstance(a, tuple)
                                          else jnp.asarray(a)
                                          for a in padded)))
    assert got.dtype == np_padded.dtype or op == "lexsort", got.dtype
    if op == "cumsum":
        # a prefix sum re-associated by the tree scan: equal up to
        # rounding
        np.testing.assert_allclose(got, np_padded, rtol=1e-12, atol=1e-12)
        np.testing.assert_allclose(real(got), want, rtol=1e-12, atol=1e-12)
    else:
        np.testing.assert_array_equal(got, np_padded)
        np.testing.assert_array_equal(real(got), want)


@requires_jax
def test_added_seed_lane_never_perturbs_existing_lanes():
    """Stacking one more seed-lane leaves the existing lanes'
    trajectories bit-identical (overflow regime included, so the depart
    heaps and the admission walk both face real flow-control traffic).
    This is the engine ``engine="jax"`` runs wherever the device program
    declines, as this overflow cell."""
    from repro.core.patterns import OVERFLOW_STRESS_DEFAULTS
    wl = get_workload("dstream")
    spec = ExperimentSpec(
        pattern="feedback", workload=wl, arch="dts", n_producers=2,
        n_consumers=2, total_messages=512,
        params=SimParams(seed=0, queue_max_bytes=64 * wl.payload_bytes,
                         **OVERFLOW_STRESS_DEFAULTS))
    two = VectorizedStreamSim(spec, stack_seeds=[0, 7]).run_stacked()
    three = VectorizedStreamSim(spec, stack_seeds=[0, 7, 99]).run_stacked()
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

    from repro.compat import scoped_x64
    from repro.core import jax_device_loop as dl
    global_x64 = jax.config.jax_enable_x64
    # large-magnitude clocks: a 1e-4 s hold on a 1e3 s base survives
    # only in float64 (f32 resolution at 1e3 is ~6e-5 and accumulates)
    x = np.concatenate([[1e3], np.full(32, 1e-4)])
    with scoped_x64():
        got = np.asarray(dl._jax_ops().cumsum(jnp.asarray(x)))
    assert got.dtype == np.float64
    np.testing.assert_allclose(got, dl._NumpyOps.cumsum(x), rtol=0,
                               atol=1e-12)
    assert np.all(np.diff(got) > 0)          # holds never vanish
    # the device program's x64 is scoped: the process-global default
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


# -- whole-run device loop --------------------------------------------------
# The wave device program (repro.core.jax_device_loop): one lax.scan
# over message generations replaces the vectorized engine's Python cohort
# loop.
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
    """jax_device_loop=None (the default) runs the vectorized engine;
    only the explicit True flag dispatches the wave program."""
    from repro.core import jax_device_loop as dl
    sim = _dl_sim(0)
    ok, why = dl._device_loop_ok(sim)
    assert ok, why
    j_default = run_many([_dl_spec(0, device=None)])[0]
    v = run_many([_dl_spec(0, engine="vectorized", device=None)])[0]
    # off the device program the jax engine is the vectorized engine,
    # bit for bit, which the wave program (different schedule) is not
    assert j_default.path == "vectorized"
    np.testing.assert_array_equal(j_default.consume_times,
                                  v.consume_times)


@requires_jax
def test_recorded_path_tells_device_program_from_cohort_path():
    """``RunResult.path`` / ``Summary.path`` name the path that ran, and
    the campaign cache key carries it, so the device program and the
    vectorized engine never share a cached row; a jax cell off the
    device program says ``"vectorized"``."""
    from repro.core.campaign import resolved_path
    dev, vec = (_dl_spec(0, pattern="work_sharing", msgs=64, device=d)
                for d in (True, None))
    assert resolved_path(dev) == "jax-device"
    assert resolved_path(vec) == "vectorized"
    rd, rv = run_many([dev])[0], run_many([vec])[0]
    assert (rd.path, summarize(rd).path) == ("jax-device", "jax-device")
    assert (rv.path, summarize(rv).path) == ("vectorized", "vectorized")
    assert summarize(rd).engine == summarize(rv).engine == "jax"
    # a device request the regime gate declines runs, and says, vectorized
    gated = _dl_spec(0, pattern="broadcast_gather", msgs=64, npr=1)
    assert resolved_path(gated) == "vectorized"
    assert run_many([gated])[0].path == "vectorized"


@requires_jax
def test_declined_device_request_stacks_as_vectorized_group():
    """A stacked jax request the regime gate declines runs as a
    vectorized group: 17 seeds chunk at the vectorized engine's 16 lanes
    (a stack of 16 and a solo run, not one stack of 17) and return what
    ``engine="vectorized"`` returns, bit for bit."""
    from repro.core import spans
    seeds = range(17)
    # a 4 x 2 feedback fleet: declined by the fleet clause
    jax_specs = [_dl_spec(s, npr=4, nc=2, msgs=64) for s in seeds]
    assert "4 x 2 fleet" in jax_engine.JaxStreamSim(
        jax_specs[0]).device_loop_why
    with spans.recording() as rec:
        got = run_many(jax_specs)
    # the group's probe and one stacked engine: the 17th seed runs solo
    assert rec.counters["streamsim.engines"] == 2
    assert "streamsim.programs" not in rec.counters
    want = run_many([_dl_spec(s, npr=4, nc=2, msgs=64, engine="vectorized",
                              device=None) for s in seeds])
    for g, w in zip(got, want):
        assert g.path == w.path == "vectorized"
        assert g.spec.params.engine == "jax"
        for f in ("consume_times", "rtts", "publish_starts"):
            np.testing.assert_array_equal(getattr(g, f), getattr(w, f),
                                          err_msg=f)


def test_device_loop_regime_gate():
    """The regime gate rejects every shape class whose wave schedule
    measurably diverges from the cohort loop, each with a reason naming
    the offending quantity (gated cells run on the vectorized engine;
    see test_declined_device_request_stacks_as_vectorized_group for the
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
