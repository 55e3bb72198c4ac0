"""Campaign layer: stacked multi-seed engine runs (run_many /
run_stacked), the declarative grid runner, and its fingerprinted cache
resume.  The stacking contract under test: the pilot lane is
bit-identical to a solo run (including its flow-control counters),
every lane conserves messages, non-pilot lanes' summaries stay within a
small tolerance of their solo equivalents (the schedule is the pilot's;
the arithmetic — including credit-backlog accounting, byte-capped
admission and reject-retry cadences — is per-lane), and overflow-regime
cells stack like everything else."""

import numpy as np
import pytest

from repro.core.campaign import CampaignSpec, CellSpec, cell_key, run_campaign
from repro.core.metrics import summarize
from repro.core.parity import band
from repro.core.patterns import sweep
from repro.core.simulator import (
    ExperimentSpec, SimParams, run_experiment)
from repro.core.vectorized import VectorizedStreamSim, run_many
from repro.core.workloads import get_workload

SEEDS = (0, 1000, 2000)


def _spec(seed, pattern="work_sharing", arch="dts", nc=4, msgs=1024, **ov):
    wl = get_workload("generic" if pattern.startswith("broadcast")
                      else "dstream")
    n_producers = 1 if pattern.startswith("broadcast") else nc
    return ExperimentSpec(pattern=pattern, workload=wl, arch=arch,
                          n_producers=n_producers, n_consumers=nc,
                          total_messages=msgs,
                          params=SimParams(seed=seed, **ov))


@pytest.mark.parametrize("pattern,msgs", [("work_sharing", 1024),
                                          ("feedback", 1024),
                                          ("broadcast_gather", 96)])
def test_stacked_pilot_exact_and_lanes_close(pattern, msgs):
    serial = [run_experiment(_spec(s, pattern, msgs=msgs)) for s in SEEDS]
    stacked = run_many([_spec(s, pattern, msgs=msgs) for s in SEEDS])
    # the pilot lane drives scheduling with its own clock: bit-identical
    assert np.array_equal(serial[0].consume_times,
                          stacked[0].consume_times)
    assert np.array_equal(serial[0].rtts, stacked[0].rtts)
    for a, b in zip(serial, stacked):
        assert b.feasible and b.n_consumed == a.n_consumed
        assert b.spec.params.seed == a.spec.params.seed
        sa, sb = summarize(a), summarize(b)
        lane_tol = band("stacked.lanes.summary")
        assert (abs(sb.throughput_msgs_s - sa.throughput_msgs_s)
                / sa.throughput_msgs_s) < lane_tol
        if a.rtts.size:
            assert (b.rtts > 0).all()
            assert (abs(sb.median_rtt_s - sa.median_rtt_s)
                    / sa.median_rtt_s) < lane_tol


def test_stacked_deterministic():
    r1 = run_many([_spec(s) for s in SEEDS])
    r2 = run_many([_spec(s) for s in SEEDS])
    for a, b in zip(r1, r2):
        assert np.array_equal(a.consume_times, b.consume_times)


def test_run_many_mixed_and_fallbacks():
    specs = [
        _spec(0),                                  # stacks with the next
        _spec(1000),
        _spec(0, engine="heap"),                   # heap: per-cell solo
        _spec(0, arch="prs-stunnel", nc=32, msgs=128),   # infeasible
        _spec(0, nc=8),                            # different shape: solo
    ]
    out = run_many(specs)
    assert [r.feasible for r in out] == [True, True, True, False, True]
    assert "connection limit" in out[3].infeasible_reason
    # heap cell really ran on the heap engine's exact path
    ref = run_experiment(_spec(0, engine="heap"))
    assert np.array_equal(out[2].consume_times, ref.consume_times)


def _overflow_specs(msgs=1024, cap_msgs=96, seeds=SEEDS):
    from repro.core.patterns import OVERFLOW_STRESS_DEFAULTS
    wl = get_workload("dstream")
    return [ExperimentSpec(
        pattern="feedback", workload=wl, arch="dts", n_producers=2,
        n_consumers=2, total_messages=msgs,
        params=SimParams(seed=s, queue_max_bytes=cap_msgs * wl.payload_bytes,
                         **OVERFLOW_STRESS_DEFAULTS)) for s in seeds]


def test_overflow_cells_stack_lane_resolved():
    """Overflow-regime cells stack like everything else: the pilot lane
    reproduces its solo run bit-for-bit (admission decisions included),
    and each other lane carries its *own* reject accounting — its own
    clocks and jitter, not a clone of the pilot's counters."""
    specs = _overflow_specs()
    stacked = run_many(specs)
    solo = run_experiment(specs[0])
    assert stacked[0].rejected_publishes == solo.rejected_publishes > 0
    assert np.array_equal(stacked[0].consume_times, solo.consume_times)
    assert np.array_equal(stacked[0].rtts, solo.rtts)
    for r in stacked:
        assert r.feasible and r.n_consumed == specs[0].total_messages
        assert r.rejected_publishes > 0
    # non-pilot lanes genuinely diverge (their own jitter streams drive
    # their own admission clocks)
    for r in stacked[1:]:
        assert not np.array_equal(r.consume_times, stacked[0].consume_times)


def test_credit_flow_cells_stack_lane_resolved():
    """Credit-flow blocking can fire without a byte cap (work queues
    always track the credit threshold); those cells stack too, with the
    pilot's blocked_confirms equal to its solo run and every lane
    reporting its own count."""
    from repro.core.patterns import OVERFLOW_STRESS_DEFAULTS
    specs = [_spec(s, "feedback", nc=2, msgs=2048,
                   **OVERFLOW_STRESS_DEFAULTS) for s in SEEDS]
    stacked = run_many(specs)
    solo = run_experiment(specs[0])
    assert stacked[0].blocked_confirms == solo.blocked_confirms > 0
    assert np.array_equal(stacked[0].consume_times, solo.consume_times)
    for r in stacked:
        assert r.feasible and r.n_consumed == 2048
        assert r.blocked_confirms > 0


def test_stack_seeds_single_lane_equals_solo_overflow():
    """``stack_seeds=[s]`` must equal ``seed=s`` exactly, including on a
    flow-control-reachable cell (the lane-resolved admission path
    collapses to the solo path at one lane)."""
    spec = _overflow_specs(seeds=(7,))[0]
    solo = run_experiment(spec)
    stacked = VectorizedStreamSim(spec, stack_seeds=[7]).run_stacked()
    assert len(stacked) == 1
    assert np.array_equal(solo.consume_times, stacked[0].consume_times)
    assert np.array_equal(solo.rtts, stacked[0].rtts)
    assert np.array_equal(solo.publish_starts, stacked[0].publish_starts)
    assert solo.rejected_publishes == stacked[0].rejected_publishes > 0
    assert solo.blocked_confirms == stacked[0].blocked_confirms


def test_stacked_overflow_pilot_determinism_regression():
    """Lane 0 of a stacked overflow run stays bit-identical to the solo
    vectorized run — every scheduling *and admission* decision is the
    pilot's own, no matter how many lanes ride along."""
    specs = _overflow_specs(msgs=768, seeds=(0, 1000, 2000, 3000))
    solo = run_experiment(specs[0])
    pilot = VectorizedStreamSim(
        specs[0], stack_seeds=[s.params.seed for s in specs]
    ).run_stacked()[0]
    assert np.array_equal(solo.consume_times, pilot.consume_times)
    assert np.array_equal(solo.rtts, pilot.rtts)
    assert np.array_equal(solo.publish_starts, pilot.publish_starts)
    assert solo.rejected_publishes == pilot.rejected_publishes
    assert solo.blocked_confirms == pilot.blocked_confirms


def test_stacked_constructor_validation():
    with pytest.raises(ValueError, match="pilot"):
        VectorizedStreamSim(_spec(0), stack_seeds=[1, 0])
    sim = VectorizedStreamSim(_spec(0), stack_seeds=[0, 1])
    with pytest.raises(RuntimeError, match="run_stacked"):
        sim.run()


# -- the declarative grid + runner ----------------------------------------


def test_campaign_cells_and_per_cell_overrides():
    spec = CampaignSpec(
        name="t", patterns=("work_sharing", "feedback"),
        architectures=("dts",), consumers=(2, 4), n_runs=2,
        total_messages=256, params={"prefetch": 32},
        cell_params=[({"pattern": "feedback"}, {"ack_batch": 2}),
                     ({"pattern": "feedback", "n_consumers": 4},
                      {"prefetch": 16})])
    cells = spec.cells()
    assert len(cells) == 2 * 2 * 2
    by = {(c.pattern, c.n_consumers, c.seed): dict(c.overrides)
          for c in cells}
    assert by[("work_sharing", 2, 0)] == {"prefetch": 32}
    assert by[("feedback", 2, 0)] == {"prefetch": 32, "ack_batch": 2}
    assert by[("feedback", 4, 1000)] == {"prefetch": 16, "ack_batch": 2}
    # JSON round trip preserves the grid
    again = CampaignSpec.from_json(spec.to_json())
    assert [cell_key(c) for c in again.cells()] == \
        [cell_key(c) for c in cells]


def test_campaign_mixed_arch_tenant_grid():
    """A tenant sweep crossed over several architectures builds one
    cell per (arch x tenants x seed) — the §6 deployment grid — and
    runs them through the batched runner."""
    spec = CampaignSpec(
        name="deploy-mini", patterns=("feedback",),
        architectures=("dts", "mss"), consumers=(4,),
        tenants=(1, 2, 4), tenant_isolation="vhost",
        n_runs=2, total_messages=256)
    cells = spec.cells()
    assert len(cells) == 2 * 3 * 2
    assert {(c.arch, c.tenants) for c in cells} == \
        {(a, t) for a in ("dts", "mss") for t in (1, 2, 4)}
    assert all(c.tenant_isolation == "vhost" for c in cells)
    # seeds of one (arch, tenants) cell group and stack together
    groups = {c.group_key() for c in cells}
    assert len(groups) == 6
    res = run_campaign(spec, workers=0)
    assert len(res.summaries) == len(cells)
    assert all(s.feasible for s in res.summaries)
    assert len(res.averaged) == 6
    assert all(s.n_runs == 2 for s in res.averaged)


def test_campaign_tenant_grid_validation_rejects_ambiguous_combos():
    """Mixing tenants > 1 with broadcast patterns or non-dividing
    consumer counts is rejected upfront with the combo named (not a
    late ExperimentSpec error deep in the grid)."""
    with pytest.raises(ValueError, match="broadcast"):
        CampaignSpec(name="bad", patterns=("feedback", "broadcast_gather"),
                     tenants=(1, 2), consumers=(4,)).cells()
    with pytest.raises(ValueError, match=r"\(6, 4\).*evenly divide"):
        CampaignSpec(name="bad2", patterns=("feedback",),
                     architectures=("dts", "mss"),
                     consumers=(4, 6), tenants=(1, 4)).cells()
    # run_campaign's upfront validation surfaces the same error
    with pytest.raises(ValueError, match="evenly divide"):
        run_campaign(CampaignSpec(name="bad3", patterns=("feedback",),
                                  consumers=(6,), tenants=(4,)),
                     workers=0)
    # tenants=1 everywhere: unaffected
    assert CampaignSpec(name="ok", patterns=("broadcast_gather",),
                        consumers=(6,), tenants=(1,)).cells()


def test_cell_key_versioned_and_distinct():
    c = CellSpec(pattern="work_sharing", arch="dts", workload="dstream",
                 n_consumers=4, total_messages=256, seed=0)
    k = cell_key(c)
    assert k.startswith("v2|engine=vectorized|")
    import dataclasses
    assert cell_key(dataclasses.replace(c, seed=1)) != k
    assert cell_key(dataclasses.replace(
        c, overrides=(("prefetch", 16),))) != k


def test_campaign_matches_serial_sweep():
    spec = CampaignSpec(name="t", patterns=("work_sharing",),
                        architectures=("dts", "mss"), consumers=(4,),
                        n_runs=3, total_messages=768)
    res = run_campaign(spec, workers=0)
    serial = sweep("work_sharing", ("dts", "mss"), "dstream",
                   consumers=(4,), n_runs=3, total_messages=768)
    assert len(res.cells) == 6 and len(res.averaged) == 2
    by = {(s.arch, s.n_consumers): s for s in res.averaged}
    for s in serial:
        c = by[(s.arch, s.n_consumers)]
        assert c.n_runs == s.n_runs == 3
        assert (abs(c.throughput_msgs_s - s.throughput_msgs_s)
                / s.throughput_msgs_s) < band("stacked.lanes.summary")


def test_campaign_cache_resume(tmp_path):
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from benchmarks.common import Cache
    spec = CampaignSpec(name="t", patterns=("work_sharing",),
                        architectures=("dts",), consumers=(2,),
                        n_runs=2, total_messages=256)
    cache = Cache(str(tmp_path / "cache.json"))
    r1 = run_campaign(spec, cache=cache, workers=0)
    assert r1.n_cached == 0
    # second run: everything served from the cache, nothing re-run
    cache2 = Cache(str(tmp_path / "cache.json"))
    r2 = run_campaign(spec, cache=cache2, workers=0)
    assert r2.n_cached == len(r2.cells) == 2
    for a, b in zip(r1.summaries, r2.summaries):
        assert a.throughput_msgs_s == b.throughput_msgs_s
    # changing a knob changes the fingerprint: cache misses again
    spec2 = CampaignSpec(name="t", patterns=("work_sharing",),
                         architectures=("dts",), consumers=(2,),
                         n_runs=2, total_messages=256,
                         params={"prefetch": 16})
    assert run_campaign(spec2, cache=cache2, workers=0).n_cached == 0


def test_average_summaries_keeps_fractional_reject_means():
    """int(np.mean(...)) used to floor a rare-overflow cell's mean
    reject count (e.g. one seed with 1 reject out of 3) to an invisible
    0 — the means must stay float."""
    from repro.core.metrics import Summary
    from repro.core.patterns import average_summaries
    base = dict(arch="dts", pattern="feedback", workload="dstream",
                n_producers=2, n_consumers=2, feasible=True)
    avg = average_summaries([Summary(**base, rejected=1, blocked=0),
                             Summary(**base, rejected=0, blocked=2),
                             Summary(**base, rejected=0, blocked=0)])
    assert avg.rejected == pytest.approx(1 / 3)
    assert avg.blocked == pytest.approx(2 / 3)
    assert avg.n_runs == 3


def test_campaign_group_is_the_cache_unit(tmp_path):
    """A partially-cached group must re-run whole: serving the partial
    hits would re-stack the remaining seeds behind a different pilot
    lane, making cached numbers depend on where a campaign was
    interrupted."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from benchmarks.common import Cache
    spec = CampaignSpec(name="t", patterns=("work_sharing",),
                        architectures=("dts",), consumers=(2,),
                        n_runs=2, total_messages=256)
    cache = Cache(str(tmp_path / "cache.json"))
    cold = run_campaign(spec, cache=cache, workers=0)
    # drop one seed's entry: the group is now partial
    victim = cell_key(cold.cells[1])
    del cache.data[victim]
    cache.save()
    resumed = run_campaign(spec, cache=Cache(str(tmp_path / "cache.json")),
                           workers=0)
    assert resumed.n_cached == 0            # whole group re-ran
    for a, b in zip(cold.summaries, resumed.summaries):
        assert a.throughput_msgs_s == b.throughput_msgs_s


def test_campaign_validates_grid_upfront():
    bad = CampaignSpec(name="t", patterns=("feedback",),
                       architectures=("dts",), consumers=(8,),
                       n_runs=1, total_messages=64, tenants=(3,))
    with pytest.raises(ValueError, match="evenly divide"):
        run_campaign(bad, workers=0)
    with pytest.raises(KeyError):
        run_campaign(CampaignSpec(name="t", workloads=("dstreamm",),
                                  n_runs=1, total_messages=64), workers=0)


def test_campaign_infeasible_cells_reported():
    spec = CampaignSpec(name="t", patterns=("work_sharing",),
                        architectures=("prs-stunnel",), consumers=(32,),
                        n_runs=2, total_messages=128)
    res = run_campaign(spec, workers=0)
    assert all(not s.feasible for s in res.summaries)
    assert not res.averaged[0].feasible and res.averaged[0].n_runs == 0


def test_campaign_engine_validated_at_construction():
    # a bad engine name fails when the spec is built, naming the source,
    # not as a bare SimParams error from deep inside run_campaign
    with pytest.raises(ValueError,
                       match=r"campaign 'bad'.*params.*unknown engine 'jaxx'"):
        CampaignSpec(name="bad", params={"engine": "jaxx"})
    with pytest.raises(
            ValueError,
            match=r"cell_params\[1\].*'arch': 'mss'.*unknown engine 'nope'"):
        CampaignSpec(name="bad2",
                     cell_params=[({"arch": "dts"}, {"prefetch": 2}),
                                  ({"arch": "mss"}, {"engine": "nope"})])
    # every registered name (importable without constructing) is fine
    ok = CampaignSpec(name="ok", params={"engine": "jax"},
                      cell_params=[({"arch": "dts"}, {"engine": "heap"})])
    assert ok.cells()
    # the from_json path re-validates too
    with pytest.raises(ValueError, match="unknown engine"):
        CampaignSpec.from_json(
            CampaignSpec(name="rt").to_json().replace(
                '"params": {}', '"params": {"engine": "typo"}'))


def test_campaign_fallback_counted_and_warned(monkeypatch):
    """A jax campaign in a jax-less environment runs vectorized — the
    result must say so (n_fallback + RuntimeWarning), never silently."""
    from repro.core import jax_engine
    monkeypatch.setattr(jax_engine, "jax_supported",
                        lambda spec: (False, "forced for test"))
    spec = CampaignSpec(name="t", patterns=("work_sharing",),
                        consumers=(2,), n_runs=2, total_messages=64,
                        params={"engine": "jax"})
    with pytest.warns(RuntimeWarning, match="fell back"):
        res = run_campaign(spec, workers=0)
    assert res.n_fallback == len(res.cells) == 2
    assert all(s.engine == "vectorized" for s in res.summaries)
    blob = __import__("json").loads(res.to_json())
    assert blob["n_fallback"] == 2
    # and the cells are keyed under the engine that actually ran
    assert all("engine=vectorized" in c["key"] for c in blob["cells"])


def test_campaign_jax_grid_runs_in_process(monkeypatch):
    """A grid with jax cells never forks: the process that holds the
    accelerator runs it, and an explicit ``workers > 1`` is refused.
    Vectorized grids keep their process pool."""
    import concurrent.futures

    pools = []

    class Pool(concurrent.futures.ThreadPoolExecutor):
        def __init__(self, max_workers=None):
            pools.append(max_workers)
            super().__init__(max_workers=max_workers)

    monkeypatch.setattr(concurrent.futures, "ProcessPoolExecutor", Pool)
    grid = dict(patterns=("work_sharing",), architectures=("dts", "mss"),
                consumers=(2,), n_runs=1, total_messages=64)
    jax_grid = CampaignSpec(name="j", params={"engine": "jax"}, **grid)
    with pytest.raises(ValueError, match="workers=2"):
        run_campaign(jax_grid, workers=2)
    res = run_campaign(jax_grid)            # workers=None: in-process
    assert pools == []
    assert all(s.engine == "jax" for s in res.summaries)
    run_campaign(CampaignSpec(name="v", **grid), workers=2)
    assert pools == [2]


def test_campaign_no_fallback_no_warning():
    spec = CampaignSpec(name="t", patterns=("work_sharing",),
                        consumers=(2,), n_runs=1, total_messages=64)
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        res = run_campaign(spec, workers=0)
    assert res.n_fallback == 0
