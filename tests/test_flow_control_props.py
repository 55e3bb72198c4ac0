"""Property tests (hypothesis) for lane-resolved flow control in the
vectorized engine.

The invariants under test, per stacked seed-lane:

* **conservation** — every published message is delivered exactly once
  (published = delivered + rejected-in-flight, and in-flight is empty
  once the run drains); reject/blocked counters are non-negative and
  zero when flow-control events are unreachable;
* **backlog cap** — the admission path never lets a lane's un-drained
  queue backlog exceed the byte cap (checked against the per-lane
  high-water mark the queue state records);
* **confirm causality / resolution** — every lane's publisher-confirm
  clock is at or after its own publish start, and the per-producer
  resolved-confirm prefix reaches the end of the run (all confirms
  finite: nothing stays withheld);
* **pilot invariance** — lane 0 of a stacked run is bit-identical to
  the solo vectorized run across sampled overflow configurations;
* the **FIFO-scan lane axis** computes exactly the per-lane solo scans
  (the identity every lane-threaded time array relies on);
* **device-program backend equivalence** — the whole-run ``lax.scan``
  wave program (:mod:`repro.core.jax_device_loop`) and its NumPy-mirror
  step loop produce identical per-generation traces over drawn shapes,
  seeds and jitter.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.chaos import (
    AutoscalePolicy, ChaosSchedule, Injection, chaos_metrics)
from repro.core.jax_engine import jax_available
from repro.core.patterns import OVERFLOW_STRESS_DEFAULTS
from repro.core.simulator import ExperimentSpec, SimParams, run_experiment
from repro.core.vectorized import VectorizedStreamSim, _fifo_scan
from repro.core.workloads import get_workload

#: every lane-resolved invariant below holds for each batched engine;
#: the device program declines flow-control cells, so the jax rows check
#: that the class ``engine="jax"`` builds keeps the vectorized engine's
#: invariants where it runs the vectorized event loop
VEC_ENGINES = (("vectorized", "jax") if jax_available()
               else ("vectorized",))


def _engine_cls(engine):
    if engine == "jax":
        from repro.core.jax_engine import JaxStreamSim
        return JaxStreamSim
    return VectorizedStreamSim


def _overflow_spec(seed, cap_msgs, msgs, nc=2):
    wl = get_workload("dstream")
    return ExperimentSpec(
        pattern="feedback", workload=wl, arch="dts", n_producers=nc,
        n_consumers=nc, total_messages=msgs,
        params=SimParams(seed=seed,
                         queue_max_bytes=cap_msgs * wl.payload_bytes,
                         **OVERFLOW_STRESS_DEFAULTS))


# -- FIFO-scan lane axis ----------------------------------------------------


@settings(max_examples=40)
@given(holds=st.lists(st.floats(min_value=0.0, max_value=5.0),
                      min_size=1, max_size=30),
       gaps=st.lists(st.floats(min_value=0.0, max_value=3.0),
                     min_size=1, max_size=30),
       scales=st.lists(st.floats(min_value=0.5, max_value=2.0),
                       min_size=2, max_size=4),
       carry=st.floats(min_value=0.0, max_value=10.0))
def test_fifo_scan_lane_axis_matches_per_lane(holds, gaps, scales, carry):
    """A lane-stacked ``_fifo_scan`` must equal running each lane's solo
    scan independently — the identity that lets one batched recurrence
    carry every seed-lane's clock."""
    n = min(len(holds), len(gaps))
    a1 = np.cumsum(np.asarray(gaps[:n]))
    h1 = np.asarray(holds[:n])
    sc = np.asarray(scales)
    a = a1[:, None] * sc[None, :]
    h = h1[:, None] * sc[None, :]
    carries = carry * sc
    got = _fifo_scan(a, h, carries)
    for lane in range(sc.size):
        want = _fifo_scan(a[:, lane], h[:, lane], carries[lane])
        assert np.allclose(got[:, lane], want, rtol=1e-12, atol=1e-12)


# -- admission-path unit properties ----------------------------------------


def _mini_sim(n_lanes, engine="vectorized"):
    spec = ExperimentSpec(
        pattern="work_sharing", workload=get_workload("dstream"),
        arch="dts", n_producers=2, n_consumers=2, total_messages=64,
        params=SimParams(seed=0, engine=engine))
    return _engine_cls(engine)(spec, stack_seeds=list(range(n_lanes)))


@pytest.mark.parametrize("engine", VEC_ENGINES)
# no deadline: an example runs many admission batches over stacked lanes
@settings(max_examples=25, deadline=None)
@given(cap=st.integers(min_value=2, max_value=12),
       lanes=st.integers(min_value=1, max_value=3),
       batches=st.lists(
           st.lists(st.floats(min_value=0.0, max_value=50.0),
                    min_size=1, max_size=12),
           min_size=1, max_size=6),
       drain_frac=st.floats(min_value=0.0, max_value=1.0))
def test_enqueue_batch_per_lane_cap_and_conservation(engine, cap, lanes,
                                                     batches, drain_frac):
    """Feeding arbitrary enqueue cohorts (with partial drains recorded
    in between) through ``_enqueue_batch`` never lets any lane's
    backlog — or its recorded high-water mark — exceed the byte cap,
    and per lane attempted == admitted + rejected at every step."""
    sim = _mini_sim(lanes, engine)
    q = sim._queue_state(("prop", 0), [0], 100, credit=3 * cap,
                         cap_msgs=cap)
    rng = np.random.default_rng(0)
    admitted = np.zeros(lanes, dtype=int)
    attempted = 0
    rejected = np.zeros(lanes, dtype=int)
    for b, times in enumerate(batches):
        base = np.sort(np.asarray(times))
        t = (base[:, None] * (1.0 + 0.05 * np.arange(lanes))
             if lanes > 1 else base)
        acc, _ = sim._enqueue_batch([q], t)
        acc2 = acc.reshape(len(times), lanes)
        admitted += acc2.sum(axis=0)
        rejected += (~acc2).sum(axis=0)
        attempted += len(times)
        assert (q["n_enq"] == admitted).all()
        assert (q["hwm"] <= cap).all()
        assert ((q["n_enq"] - q["departed"]) <= cap).all()
        # drain a fraction of what each lane has admitted and not yet
        # released: recording more departs than admitted messages would
        # let a lane's backlog go negative
        unreleased = q["n_enq"] - q["released"]
        n_drain = int(drain_frac * unreleased.min())
        if n_drain:
            d = np.cumsum(rng.uniform(0.1, 2.0, (n_drain, lanes)), axis=0) \
                + float(np.max(t))
            sim._record_departs(q, d if lanes > 1 else d[:, 0])
    assert attempted * lanes == int(admitted.sum() + rejected.sum())


# -- whole-run lane invariants under overflow ------------------------------


@pytest.mark.parametrize("engine", VEC_ENGINES)
@settings(max_examples=5, deadline=None)
@given(seeds=st.lists(st.integers(min_value=1, max_value=10_000),
                      min_size=1, max_size=3),
       cap_msgs=st.integers(min_value=48, max_value=128),
       msgs=st.sampled_from((256, 512)))
def test_stacked_overflow_lane_invariants(engine, seeds, cap_msgs, msgs):
    """Whole-run invariants of a stacked overflow cell, per lane:
    conservation, non-negative lane-resolved counters, positive RTTs,
    confirm causality + full confirm resolution, backlog high-water
    marks within the cap, drained queues, and a bit-identical pilot
    (the solo reference stays on the vectorized engine, so the jax
    param also pins the jax pilot to the vectorized solo run)."""
    spec = _overflow_spec(0, cap_msgs, msgs)
    sim = _engine_cls(engine)(spec, stack_seeds=[0] + seeds)
    results = sim.run_stacked()
    solo = run_experiment(spec)
    # pilot invariance: the admission path collapses to the solo one
    assert np.array_equal(results[0].consume_times, solo.consume_times)
    assert results[0].rejected_publishes == solo.rejected_publishes
    assert results[0].blocked_confirms == solo.blocked_confirms
    for r in results:
        assert r.feasible
        # conservation: published = delivered (+ empty in-flight)
        assert r.n_consumed == msgs
        assert r.publish_starts.size == msgs
        assert r.rtts.size == msgs and (r.rtts > 0).all()
        assert r.rejected_publishes >= 0 and r.blocked_confirms >= 0
    # per-lane confirm causality + resolution (prefix reached the end)
    conf, pub = sim._fin_confirms, sim._fin_pub
    assert np.isfinite(conf).all()
    assert (conf >= pub - 1e-12).all()
    # per-lane queue accounting: drained, capped, nothing withheld
    for q in sim._queues.values():
        if not q["track"]:
            continue
        assert not q["deferred"]
        assert (q["n_enq"] == q["released"]).all()
        assert (q["departed"] <= q["released"]).all()
        if q["cap"] is not None:
            # the pilot's admission is exact; non-pilot lanes may
            # overshoot only by their counted optimistic admissions
            # (a lane at cap with no known future drain admits on the
            # next retry instead of deferring its pilot-fixed schedule)
            assert q["hwm"][0] <= q["cap"]
            assert (q["hwm"] <= q["cap"] + q["forced"]).all()


# -- whole-run device program: backend equivalence --------------------------


@pytest.mark.slow
@pytest.mark.skipif(not jax_available(), reason="jax required")
@settings(max_examples=8, deadline=None)
@given(pattern=st.sampled_from(("work_sharing", "feedback")),
       npr=st.sampled_from((2, 4)),
       msgs_per=st.sampled_from((16, 32)),
       jitter=st.floats(min_value=0.0, max_value=0.05),
       seed=st.integers(min_value=0, max_value=999))
def test_device_trace_jax_matches_numpy_step_for_step(pattern, npr,
                                                      msgs_per, jitter,
                                                      seed):
    """The jitted ``lax.scan`` device program and its NumPy-mirror step
    loop (``backend="numpy"``) emit identical per-generation traces for
    arbitrary drawn shapes, seeds and jitter — the numpy mirror is the
    step-for-step oracle of :mod:`repro.core.jax_device_loop`, so any
    divergence is a jit/vmap artifact, never modeling noise."""
    from repro.core import jax_device_loop as dl
    spec = ExperimentSpec(
        pattern=pattern, workload=get_workload("dstream"), arch="dts",
        n_producers=npr, n_consumers=2,
        total_messages=npr * msgs_per,
        params=SimParams(seed=seed, jitter=jitter))
    sim = VectorizedStreamSim(spec)
    ws = dl.build_static(sim)
    jit = dl.draw_jitter(sim, ws)
    yn = dl.run_wave_trace(ws, jit, backend="numpy")
    yj = dl.run_wave_trace(ws, jit, backend="jax")
    assert set(yn) == set(yj)
    for k in sorted(yn):
        np.testing.assert_allclose(yj[k], yn[k], rtol=1e-12,
                                   atol=1e-12, err_msg=k)


# -- chaos epoch boundaries: conservation properties ------------------------


def _chaos_spec(engine, seed, chaos, nc=2):
    return ExperimentSpec(
        pattern="work_sharing", workload=get_workload("generic"),
        arch="dts", n_producers=2, n_consumers=nc, total_messages=256,
        params=SimParams(seed=seed, engine=engine, jitter=0.0,
                         consumer_proc_s=2e-3, chaos=chaos))


@pytest.mark.parametrize("engine", ("heap", "vectorized"))
@settings(max_examples=6, deadline=None)
@given(kind=st.sampled_from(("link", "broker", "consumer")),
       t0=st.floats(min_value=0.5, max_value=4.0),
       dur=st.floats(min_value=0.5, max_value=6.0),
       seed=st.integers(min_value=0, max_value=99))
def test_chaos_epoch_conservation(engine, kind, t0, dur, seed):
    """Full state carry-over across drawn epoch boundaries, in both
    engines: every message still completes (delayed, never dropped),
    every completion beyond the unique count is a redelivered
    duplicate, and completion times stay inside the run."""
    target = {"link": "dsn_in:0", "broker": "queue:work:0",
              "consumer": "c1"}[kind]
    sched = ChaosSchedule(injections=(
        Injection(kind, target, t0, t0 + dur),))
    r = run_experiment(_chaos_spec(engine, seed, sched))
    assert r.feasible
    m = chaos_metrics(r, sched)
    assert m.lost == 0
    assert r.n_consumed == 256 + m.duplicates
    assert m.duplicates <= r.redelivered
    ts = r.consume_times
    assert np.isfinite(ts).all()
    assert ts.size and float(ts.max()) <= r.sim_time + 1e-9


@pytest.mark.parametrize("engine", ("heap", "vectorized"))
@settings(max_examples=6, deadline=None)
@given(high=st.integers(min_value=8, max_value=48),
       low=st.integers(min_value=0, max_value=7),
       step=st.integers(min_value=1, max_value=3),
       max_c=st.sampled_from((4, 6, 8)),
       seed=st.integers(min_value=0, max_value=99))
def test_chaos_autoscale_conservation(engine, high, low, step, max_c,
                                      seed):
    """Backlog-reactive autoscaling never loses or duplicates work, for
    drawn policies in both engines — growing/retiring consumers is pure
    topology change, not message mutation."""
    sched = ChaosSchedule(autoscale=AutoscalePolicy(
        interval_s=0.25, high_backlog=high, low_backlog=low,
        max_consumers=max_c, step=step))
    r = run_experiment(_chaos_spec(engine, seed, sched))
    assert r.feasible
    assert r.n_consumed == 256
    assert r.redelivered == 0
    assert np.isfinite(r.consume_times).all()


@pytest.mark.parametrize("engine", VEC_ENGINES)
@settings(max_examples=4, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000),
       nc=st.sampled_from((2, 4)))
def test_no_flow_events_means_zero_counters_every_lane(engine, seed, nc):
    """With no byte cap and no reachable credit threshold, every lane's
    flow-control counters must be exactly zero (the lane-resolved
    admission path must not invent events)."""
    wl = get_workload("dstream")
    spec = ExperimentSpec(
        pattern="work_sharing", workload=wl, arch="dts", n_producers=nc,
        n_consumers=nc, total_messages=512,
        params=SimParams(seed=seed, engine=engine))
    sim = _engine_cls(engine)(spec, stack_seeds=[seed, seed + 1,
                                                 seed + 2])
    assert not sim.flow_events_possible()
    for r in sim.run_stacked():
        assert r.rejected_publishes == 0
        assert r.blocked_confirms == 0
        assert r.n_consumed == 512
