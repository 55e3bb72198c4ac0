"""Benchmark cache keying: engine/params-aware keys, loud failure on
legacy-format entries (the bug where an engine switch silently served
stale heap-engine numbers from results/bench_cache.json)."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks.common import (  # noqa: E402
    Cache, LegacyCacheError, cache_key, params_fingerprint, plain_key,
    resolve_engine)
from repro.core.simulator import SimParams  # noqa: E402


def test_cache_key_includes_engine_and_fingerprint():
    kh = cache_key("work_sharing|dts|dstream|8|4096|1", engine="heap")
    kv = cache_key("work_sharing|dts|dstream|8|4096|1", engine="vectorized")
    assert kh != kv
    assert "engine=heap" in kh and "engine=vectorized" in kv
    assert kh.startswith("v2|") and kv.startswith("v2|")


def test_fingerprint_tracks_param_overrides_and_defaults():
    base = params_fingerprint("vectorized")
    assert params_fingerprint("vectorized", prefetch=16) != base
    assert params_fingerprint("vectorized", jitter=0.0) != base
    # stable for identical input
    assert params_fingerprint("vectorized") == base


def test_resolve_engine_defaults_to_simparams_default():
    assert resolve_engine(None) == SimParams().engine == "vectorized"
    assert resolve_engine("heap") == "heap"


def test_legacy_cache_fails_loudly(tmp_path):
    p = tmp_path / "bench_cache.json"
    p.write_text(json.dumps({
        "work_sharing|dts|dstream|8|4096|1|": {"throughput": 1.0}}))
    with pytest.raises(LegacyCacheError, match="legacy-format"):
        Cache(str(p))


def test_versioned_cache_roundtrip_and_key_guard(tmp_path):
    p = tmp_path / "bench_cache.json"
    c = Cache(str(p))
    k = cache_key("cell", engine="vectorized")
    assert c.get_or(k, lambda: {"v": 1}) == {"v": 1}
    # served from disk on reload, no recompute
    c2 = Cache(str(p))
    assert c2.get_or(k, lambda: {"v": 2}) == {"v": 1}
    # unversioned keys are rejected at write time too
    with pytest.raises(LegacyCacheError, match="version prefix"):
        c2.get_or("raw-key", lambda: {})
    assert plain_key("kernels/micro").startswith("v2|")


# ---------------------------------------------------------------------------
# Fallback-engine resolution (the cache-poisoning regression): a jax
# cell the run_many fallback downgrades to vectorized must be keyed as
# vectorized — sharing the entry with the identical genuine-vectorized
# cell — and must never occupy the jax namespace.  The genuinely-jax
# cells request the device program on a cell its gate admits: a jax
# cell off the device program runs, and is keyed as, vectorized.
# ---------------------------------------------------------------------------


def _force_fallback(monkeypatch):
    from repro.core import jax_engine
    monkeypatch.setattr(jax_engine, "jax_supported",
                        lambda spec: (False, "forced for test"))


def test_cache_key_resolves_fallback_engine(monkeypatch):
    from repro.core.patterns import pattern_spec
    spec = pattern_spec("work_sharing", "dts", "dstream", 2,
                        total_messages=8, engine="jax",
                        jax_device_loop=True)
    assert "engine=jax" in cache_key("cell", engine="jax", spec=spec)
    _force_fallback(monkeypatch)
    kf = cache_key("cell", engine="jax", spec=spec)
    assert "engine=jax" not in kf
    # key AND fingerprint match the identical genuine-vectorized cell:
    # same computation, one cache entry
    assert kf == cache_key("cell", engine="vectorized")


def test_cell_key_resolves_fallback_engine(monkeypatch):
    from repro.core.campaign import CellSpec, cell_key
    cj = CellSpec(pattern="work_sharing", arch="dts", workload="dstream",
                  n_consumers=2, total_messages=8, seed=0,
                  overrides=(("engine", "jax"), ("jax_device_loop", True)))
    cv = CellSpec(pattern="work_sharing", arch="dts", workload="dstream",
                  n_consumers=2, total_messages=8, seed=0,
                  overrides=(("engine", "vectorized"),
                             ("jax_device_loop", True)))
    assert "engine=jax" in cell_key(cj)
    _force_fallback(monkeypatch)
    assert cell_key(cj) == cell_key(cv)


def test_fallback_cells_never_poison_jax_namespace(tmp_path, monkeypatch):
    from benchmarks.common import sim_cell
    c = Cache(str(tmp_path / "cache.json"))
    _force_fallback(monkeypatch)
    cell = sim_cell(c, "work_sharing", "dts", "dstream", 2, 64,
                    engine="jax")
    assert cell["feasible"]
    assert all("engine=jax" not in k for k in c.data)
    assert any("engine=vectorized" in k for k in c.data)
    # the identical vectorized cell is a HIT on the fallback's entry
    assert sim_cell(c, "work_sharing", "dts", "dstream", 2, 64,
                    engine="vectorized") == cell
    assert len(c.data) == 1
    # once jax is genuinely available, the jax cell's key lands in the
    # jax namespace — a cache MISS, never served the vectorized numbers
    monkeypatch.undo()
    from repro.core.patterns import pattern_spec
    spec = pattern_spec("work_sharing", "dts", "dstream", 2,
                        total_messages=64, engine="jax",
                        jax_device_loop=True)
    kj = cache_key("work_sharing|dts|dstream|2|64|1", engine="jax",
                   spec=spec)
    assert "engine=jax" in kj and kj not in c.data


def test_declined_jax_cell_shares_the_vectorized_row():
    """A jax cell off the device program runs the vectorized engine, so
    it is keyed as the identical vectorized cell, with jax available:
    one cache row for one computation."""
    from repro.core.campaign import CellSpec, cell_key
    from repro.core.patterns import pattern_spec

    def cell(engine):
        return CellSpec(pattern="work_sharing", arch="dts",
                        workload="dstream", n_consumers=2,
                        total_messages=8, seed=0,
                        overrides=(("engine", engine),))
    assert cell_key(cell("jax")) == cell_key(cell("vectorized"))
    spec = pattern_spec("work_sharing", "dts", "dstream", 2,
                        total_messages=8, engine="jax")
    assert (cache_key("cell", engine="jax", spec=spec)
            == cache_key("cell", engine="vectorized"))
