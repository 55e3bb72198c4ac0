"""Heap-engine vs vectorized-engine parity on the paper's Fig 4/6/7
metrics: throughput (work sharing), median RTT (feedback), broadcast
throughput + gather RTT — all three architectures at 8 consumers — plus
an overflow-regime block (reject-publish + credit-flow blocking active)
and property tests of the FIFO-scan carry math.

The previously-documented outliers (DTS work-sharing throughput, DTS/PRS
gather RTTs at ~5-7%) are closed to <=3% by the vectorized engine's
utilization-triggered finer interleaving and its virtual-time window
assignment — see repro/core/vectorized.py.  Bounds here carry margin
over the measured deviations so the suite stays robust across platforms.
"""

import functools

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.metrics import overhead_vs_baseline, summarize
from repro.core.parity import band, factor_band
from repro.core.patterns import (
    OVERFLOW_STRESS_DEFAULTS, average_summaries, overflow_stress,
    run_pattern)
from repro.core.simulator import (
    ENGINES, SimConfig, SimParams, get_engine)
from repro.core.vectorized import _fifo_scan

ARCHS = ("dts", "prs-haproxy", "mss")
NC = 8

#: every engine held to the heap reference's parity bands; these cells
#: do not request the device program, so the jax rows check that the
#: class ``engine="jax"`` builds keeps the vectorized engine's results
#: (and tolerances) where the device program declines.  Without jax
#: importable the jax column drops out (run_many would fall back to
#: vectorized anyway, making the rows redundant).
from repro.core.jax_engine import jax_available  # noqa: E402

VEC_ENGINES = (("vectorized", "jax") if jax_available()
               else ("vectorized",))

#: per-cell relative tolerances, read from the single source of truth
#: in repro.core.parity (the docs table and the streamlint docs-drift
#: rule read the same constants); the residuals that sat at 5-7% (DTS
#: work-sharing throughput, DTS feedback RTT, PRS gather RTT) are closed
#: to <=3% by saturation-triggered fine interleaving + virtual-time
#: window assignment in the batched pump
THR_TOL = {a: band(f"work_sharing.{a}.throughput") for a in ARCHS}
RTT_TOL = {a: band(f"feedback.{a}.median_rtt") for a in ARCHS}
GATHER_RTT_TOL = {a: band(f"broadcast_gather.{a}.gather_rtt")
                  for a in ARCHS}


@functools.lru_cache(maxsize=None)
def _cell(pattern, arch, wl, msgs, engine):
    # cached: the heap reference cells are shared across every
    # parameterized engine comparing against them
    r = run_pattern(pattern, arch, wl, NC, total_messages=msgs, n_runs=1,
                    seed=0, jitter=0.0, engine=engine)[0]
    assert r.feasible
    return summarize(r)


def _rel(a, b):
    return abs(b - a) / a


@pytest.mark.parametrize("engine", VEC_ENGINES)
@pytest.mark.parametrize("arch", ARCHS)
def test_work_sharing_throughput_parity(arch, engine):
    """Fig 4: aggregate work-sharing throughput."""
    h = _cell("work_sharing", arch, "dstream", 4096, "heap")
    v = _cell("work_sharing", arch, "dstream", 4096, engine)
    assert v.n_messages == h.n_messages == 4096
    assert _rel(h.throughput_msgs_s, v.throughput_msgs_s) < THR_TOL[arch]


@pytest.mark.parametrize("engine", VEC_ENGINES)
@pytest.mark.parametrize("arch", ARCHS)
def test_feedback_rtt_parity(arch, engine):
    """Fig 6: feedback median RTT (and throughput rides along)."""
    h = _cell("feedback", arch, "dstream", 4096, "heap")
    v = _cell("feedback", arch, "dstream", 4096, engine)
    assert _rel(h.median_rtt_s, v.median_rtt_s) < RTT_TOL[arch]
    assert _rel(h.throughput_msgs_s,
                v.throughput_msgs_s) < band("feedback.all.throughput")


@pytest.mark.parametrize("engine", VEC_ENGINES)
@pytest.mark.parametrize("arch", ARCHS)
def test_broadcast_gather_parity(arch, engine):
    """Fig 7: broadcast throughput + gather RTT."""
    h = _cell("broadcast_gather", arch, "generic", 400, "heap")
    v = _cell("broadcast_gather", arch, "generic", 400, engine)
    assert v.n_messages == h.n_messages == 400 * NC
    assert _rel(h.throughput_msgs_s, v.throughput_msgs_s) < band(
        "broadcast_gather.all.throughput")
    assert _rel(h.median_rtt_s, v.median_rtt_s) < GATHER_RTT_TOL[arch]


def test_overhead_ratios_preserved():
    """The paper's §5.2 overhead-vs-DTS ratios survive the engine swap."""
    thr = {}
    for eng in ("heap",) + VEC_ENGINES:
        for arch in ARCHS:
            thr[eng, arch] = _cell(
                "work_sharing", arch, "dstream", 4096, eng).throughput_msgs_s
    for eng in ("heap",) + VEC_ENGINES:
        ov_mss = overhead_vs_baseline(thr[eng, "mss"], thr[eng, "dts"],
                                      higher_is_better=True)
        ov_prs = overhead_vs_baseline(thr[eng, "prs-haproxy"],
                                      thr[eng, "dts"], higher_is_better=True)
        # paper: MSS pays a clear work-sharing throughput overhead; PRS
        # sits between DTS and MSS
        assert ov_mss > ov_prs > 1.0


# -- overflow regime: reject-publish + credit-flow blocking ----------------


@functools.lru_cache(maxsize=None)
def _overflow_heap():
    return overflow_stress("dts", 4, jitter=0.0, engine="heap")[0]


#: seed -> solo heap RunResult for the stacked-overflow test below
_STACKED_OVERFLOW_HEAP_CACHE: dict = {}


@pytest.mark.parametrize("engine", VEC_ENGINES)
def test_overflow_regime_parity(engine):
    """A regime the paper's configs never trigger: tight queue caps, a
    small confirm window and slow consumers force reject-publish overflow
    AND credit-flow confirm withholding in the heap engine; the batched
    engines must reproduce throughput and median RTT within 5%
    and the rejected/blocked counters within a small tolerance."""
    h = _overflow_heap()
    v = overflow_stress("dts", 4, jitter=0.0, engine=engine)[0]
    # the heap engine actually exercises both mechanisms
    assert h.rejected_publishes > 0
    assert h.blocked_confirms > 0
    assert v.n_consumed == h.n_consumed
    hs, vs = summarize(h), summarize(v)
    summary_tol = band("overflow.dts.summary")
    counter_tol = band("overflow.dts.counters")
    assert _rel(hs.throughput_msgs_s, vs.throughput_msgs_s) < summary_tol
    assert _rel(hs.median_rtt_s, vs.median_rtt_s) < summary_tol
    # counter parity: both mechanisms fire, with closely matching volume
    assert v.rejected_publishes > 0
    assert v.blocked_confirms > 0
    assert _rel(h.rejected_publishes, v.rejected_publishes) < counter_tol
    assert _rel(h.blocked_confirms, v.blocked_confirms) < counter_tol


@pytest.mark.parametrize("engine", VEC_ENGINES)
def test_stacked_overflow_lanes_match_solo_heap(engine):
    """Stacked execution of an overflow-regime cell is lane-resolved:
    every lane — not just the pilot — must land within tolerance of its
    own solo *heap* run.  Summaries are tight (<=5%); the reject/block
    counters are knife-edge threshold counts that swing with the jitter
    realization in both engines, so they get a factor band plus a
    hard nonzero requirement (both mechanisms must fire in every
    lane)."""
    from repro.core.simulator import ExperimentSpec, run_experiment
    from repro.core.vectorized import run_many
    from repro.core.workloads import get_workload
    from repro.core.broker import ClassicQueue
    wl = get_workload("dstream")
    cap = int(ClassicQueue.FLOW_CREDIT * 4 * 1.06) * wl.payload_bytes
    seeds = (0, 1000, 2000)

    def spec(s, eng):
        return ExperimentSpec(
            pattern="feedback", workload=wl, arch="dts", n_producers=4,
            n_consumers=4, total_messages=8192,
            params=SimParams(seed=s, engine=eng, queue_max_bytes=cap,
                             **OVERFLOW_STRESS_DEFAULTS))

    # the per-seed heap references are shared across the engine params
    cache = _STACKED_OVERFLOW_HEAP_CACHE

    stacked = run_many([spec(s, engine) for s in seeds])
    assert len({id(r) for r in stacked}) == 3
    summary_tol = band("stacked_overflow.lanes.summary")
    rej_lo, rej_hi = factor_band("stacked_overflow.lanes.rejected")
    blk_lo, blk_hi = factor_band("stacked_overflow.lanes.blocked")
    for s, v in zip(seeds, stacked):
        if s not in cache:
            cache[s] = run_experiment(spec(s, "heap"))
        h = cache[s]
        assert h.rejected_publishes > 0 and h.blocked_confirms > 0
        assert v.n_consumed == h.n_consumed == 8192
        hs, vs = summarize(h), summarize(v)
        assert _rel(hs.throughput_msgs_s,
                    vs.throughput_msgs_s) < summary_tol, s
        assert _rel(hs.median_rtt_s, vs.median_rtt_s) < summary_tol, s
        # lane-resolved counters: nonzero in every lane, same order of
        # magnitude as the lane's own heap realization
        assert v.rejected_publishes > 0 and v.blocked_confirms > 0
        assert (rej_lo < v.rejected_publishes / h.rejected_publishes
                < rej_hi), s
        assert (blk_lo < v.blocked_confirms / h.blocked_confirms
                < blk_hi), s


def test_overflow_guaranteed_delivery_both_engines():
    """Rejected publishes are retried until accepted: every message is
    still consumed exactly once (paper §6 guaranteed delivery)."""
    for eng in ("heap",) + VEC_ENGINES:
        r = overflow_stress("dts", 2, total_messages=4096, engine=eng)[0]
        assert r.rejected_publishes > 0, eng
        assert r.n_consumed == 4096, eng


def test_queue_cap_below_one_message_is_infeasible():
    """A cap that cannot hold a single message would otherwise spin on
    reject-retry until max_sim_time and report an empty feasible run."""
    for eng in ("heap",) + VEC_ENGINES:
        r = run_pattern("work_sharing", "dts", "dstream", 2,
                        total_messages=8, n_runs=1, engine=eng,
                        queue_max_bytes=1)[0]
        assert not r.feasible, eng
        assert "queue_max_bytes" in r.infeasible_reason


def test_overflow_regime_scales_on_vectorized():
    """The stress scenario stays exercisable at consumer counts far past
    the paper sweep (vectorized only; the heap engine would need minutes)."""
    r = overflow_stress("dts", 64, queue_cap_msgs=512,
                        total_messages=4096, consumer_proc_s=16e-3,
                        engine="vectorized")[0]
    assert r.feasible and r.n_consumed == 4096
    assert r.rejected_publishes > 0


# -- FIFO-scan carry math (property-tested) --------------------------------


def _fifo_ref(a, h, carry):
    """Sequential reference: e_j = max(a_j, e_{j-1}) + h_j."""
    e = carry
    out = []
    for ai, hi in zip(a, h):
        e = max(ai, e) + hi
        out.append(e)
    return np.array(out)


@settings(max_examples=50)
@given(holds=st.lists(st.floats(min_value=0.0, max_value=5.0),
                      min_size=1, max_size=40),
       gaps=st.lists(st.floats(min_value=0.0, max_value=3.0),
                     min_size=1, max_size=40),
       carry=st.floats(min_value=0.0, max_value=20.0))
def test_fifo_scan_matches_sequential_reference(holds, gaps, carry):
    n = min(len(holds), len(gaps))
    a = np.cumsum(np.asarray(gaps[:n]))          # sorted arrivals
    h = np.asarray(holds[:n])
    got = _fifo_scan(a, h, carry)
    want = _fifo_ref(a, h, carry)
    assert np.allclose(got, want, rtol=1e-12, atol=1e-12)


@settings(max_examples=50)
@given(holds=st.lists(st.floats(min_value=0.0, max_value=5.0),
                      min_size=2, max_size=40),
       gaps=st.lists(st.floats(min_value=0.0, max_value=3.0),
                     min_size=2, max_size=40),
       cut_frac=st.floats(min_value=0.0, max_value=1.0))
def test_fifo_scan_carry_composes_across_batches(holds, gaps, cut_frac):
    """Serving a FIFO batch in two chunks with the carry threaded through
    equals serving it at once — the invariant the batched engine relies
    on every time a cohort is split at the event horizon."""
    n = min(len(holds), len(gaps))
    a = np.cumsum(np.asarray(gaps[:n]))
    h = np.asarray(holds[:n])
    whole = _fifo_scan(a, h, 0.0)
    k = min(n - 1, max(1, int(n * cut_frac)))
    first = _fifo_scan(a[:k], h[:k], 0.0)
    second = _fifo_scan(a[k:], h[k:], float(first[-1]))
    assert np.allclose(np.concatenate([first, second]), whole,
                       rtol=1e-12, atol=1e-12)


# -- engine selection / config validation ----------------------------------


def test_vectorized_deterministic_and_seed_sensitive():
    kw = dict(total_messages=2048, n_runs=1, engine="vectorized")
    r1 = run_pattern("work_sharing", "dts", "dstream", NC, seed=3, **kw)[0]
    r2 = run_pattern("work_sharing", "dts", "dstream", NC, seed=3, **kw)[0]
    r3 = run_pattern("work_sharing", "dts", "dstream", NC, seed=4, **kw)[0]
    assert np.array_equal(r1.consume_times, r2.consume_times)
    assert not np.array_equal(r1.consume_times, r3.consume_times)


def test_vectorized_respects_feasibility_gates():
    r = run_pattern("work_sharing", "prs-stunnel", "dstream", 32,
                    total_messages=512, n_runs=1, engine="vectorized")[0]
    assert not r.feasible and "connection limit" in r.infeasible_reason


def test_engine_registry_and_vectorized_default():
    assert SimConfig is SimParams
    assert SimConfig().engine == "vectorized"      # the default engine
    assert get_engine("heap") is ENGINES["heap"]
    assert get_engine("vectorized") is ENGINES["vectorized"]
    assert get_engine("jax") is ENGINES["jax"]   # registers without jax
    with pytest.raises(ValueError):
        get_engine("quantum")


def test_simparams_validation():
    with pytest.raises(ValueError, match="unknown engine"):
        SimParams(engine="quantum")
    with pytest.raises(ValueError, match="vec_round"):
        SimParams(vec_round=0)
    with pytest.raises(ValueError, match="exceeds the confirm window"):
        SimParams(vec_round=256, confirm_window=128)
    with pytest.raises(ValueError, match="sub-multiple"):
        SimParams(vec_round=7, confirm_window=128)
    with pytest.raises(ValueError, match="queue_max_bytes"):
        SimParams(queue_max_bytes=0)
    with pytest.raises(ValueError, match="vec_horizon_s"):
        SimParams(vec_horizon_s=-1.0)
    with pytest.raises(ValueError, match="confirm_window"):
        SimParams(confirm_window=1)
    # valid configs construct, including the auto (None) knobs
    assert SimParams().vec_round is None
    assert SimParams(vec_round=8, confirm_window=64).vec_round == 8


def test_run_pattern_validates_overrides():
    with pytest.raises(ValueError):
        run_pattern("work_sharing", "dts", "dstream", 2,
                    total_messages=64, n_runs=1, engine="quantum")
    with pytest.raises(ValueError):
        run_pattern("work_sharing", "dts", "dstream", 2,
                    total_messages=64, n_runs=1, vec_round=0)


def test_average_summaries_mixed_feasibility():
    """A mixed-feasibility cell must not report a single seed's metrics
    as a multi-run mean: average the feasible subset and record n_runs."""
    ok = _cell("work_sharing", "dts", "dstream", 256, "vectorized")
    bad = summarize(run_pattern("work_sharing", "prs-stunnel", "dstream", 32,
                                total_messages=256, n_runs=1,
                                engine="vectorized")[0])
    mixed = average_summaries([ok, bad, ok])
    assert mixed.feasible and mixed.n_runs == 2
    assert np.isclose(mixed.throughput_msgs_s, ok.throughput_msgs_s)
    none = average_summaries([bad, bad])
    assert not none.feasible and none.n_runs == 0


def test_vectorized_conserves_messages_across_patterns():
    for pattern, wl, msgs, expect in (
            ("work_sharing", "dstream", 1024, 1024),
            ("feedback", "dstream", 1024, 1024),
            ("broadcast", "generic", 64, 64 * NC),
            ("broadcast_gather", "generic", 64, 64 * NC)):
        r = run_pattern(pattern, "dts", wl, NC, total_messages=msgs,
                        n_runs=1, engine="vectorized")[0]
        assert r.n_consumed == expect, pattern
        if pattern in ("feedback", "broadcast_gather"):
            assert r.rtts.size == expect and (r.rtts > 0).all()
