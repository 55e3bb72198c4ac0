"""The benchmark's run on the CPU: it refuses without a TPU, a cell made
of dropped-in files runs end to end with the chip check stubbed, and a
result off the device program fails the run."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import numpy as np

from _tiny import CELL, ROOT, args, make_checkout, stub_chip, tiny_config

from chipbench import check, generator, run, trace

BENCH_CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]


def test_cli_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "deploy16.paper3",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "not a TPU" in p.stderr


def test_cli_fails_without_the_program(tmp_path):
    (tmp_path / "chipbench").symlink_to(ROOT / "chipbench")
    (tmp_path / "BENCHMARK.json").write_text(
        (ROOT / "BENCHMARK.json").read_text())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, '.'); import chipbench.run as r; "
         "r.device_check = lambda chips: ('tpu', 'TPU v5 lite', 1); "
         "r.compile_cache = lambda root: 'off'; r.main()",
         ], cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""


def test_dropped_in_cell_runs_and_is_correct(tmp_path, monkeypatch):
    root = make_checkout(tmp_path)
    stub_chip(monkeypatch)
    out = run.run(args(seed=3_000_000_007), root=str(root))
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"sim_msgs_per_s", "request_p90_s",
                                   "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"
    json.dumps(out)


def test_traced_run_reports_layers_and_a_dropped_in_reader(
        tmp_path, monkeypatch):
    from repro.core import jax_device_loop as dl
    root = make_checkout(tmp_path, extra_layer="run_program_ms")
    stub_chip(monkeypatch)
    for name in run.SPANS:      # the run wraps these; restored afterwards
        monkeypatch.setattr(dl, name, getattr(dl, name))
    # the CPU trace has no device plane: stand in one busy interval per
    # request annotation, so the rest of the reduction runs
    real = trace.read_xplane

    def read(path, names):
        tr = real(path, names)
        reqs = [a for a in tr.annotations if a[0] == trace.REQUEST]
        tr.ops = [(0, "fusion", s + (e - s) // 4, e - (e - s) // 4)
                  for _, s, e in reqs]
        tr.n_devices = 1
        return tr
    monkeypatch.setattr(trace, "read_xplane", read)
    monkeypatch.setattr(run, "device_check",
                        lambda chips: ("cpu", "TPU v5 lite", 1))
    out = run.run(args(seconds=1.0, trace=1), root=str(root))
    assert out["correct"], out["checks"]
    m = out["metrics"]
    for name in ("entry_host_ms", "static_build_ms", "assemble_ms",
                 "device_busy_ms", "device_idle_pct",
                 "scan_hbm_roofline_pct", "run_program_ms"):
        assert m[name]["value"] > 0, name
    assert 45 < m["device_idle_pct"]["value"] < 55
    assert 0 < out["device"]["busy_s"] < out["device"]["window_s"]
    assert out["breakdown"]["device_ops"][0][0] == "fusion"
    assert len(out["breakdown"]["idle_gaps"]) <= 10


@pytest.mark.parametrize("edit", [
    ("inventory", "client_link_gbps", 0.5),
    ("inventory", "n_dsn", 2),
    ("workload", "consumer_proc_s", 2.4e-4),
])
def test_configuration_file_reaches_the_program(tmp_path, monkeypatch, edit):
    """A deployment that differs from the program's defaults runs as its
    file states: the reference, which reads the file, agrees."""
    ref = check.load_reference(str(ROOT), "work_sharing")
    moved = [np.abs(ref.simulate(tiny_config(edit), arch, [11])["consume"]
                    - ref.simulate(tiny_config(), arch, [11])["consume"]).max()
             for arch in tiny_config()["architectures"]]
    assert max(moved) > 1e-6
    root = make_checkout(tmp_path, edit=edit)
    stub_chip(monkeypatch)
    out = run.run(args(seconds=0.1), root=str(root))
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("edit", [
    ("calibration", "client_link_eff", 0.9),
    ("calibration", "lb_servers", 8),
    ("calibration", "no_such_constant", 1.0),
])
def test_calibration_the_program_does_not_run_is_refused(tmp_path,
                                                         monkeypatch, edit):
    root = make_checkout(tmp_path, edit=edit)
    stub_chip(monkeypatch)
    with pytest.raises(SystemExit, match=edit[1]):
        run.run(args(seconds=0.1), root=str(root))


def test_result_off_the_device_program_fails_the_run(tmp_path, monkeypatch):
    from repro.core.jax_engine import JaxStreamSim
    root = make_checkout(tmp_path)
    stub_chip(monkeypatch)
    monkeypatch.setattr(JaxStreamSim, "_use_device_loop", lambda self: False)
    out = run.run(args(seconds=0.1), root=str(root))
    assert not out["correct"]
    assert out["checks"]["off_device_path"][0] > 0
    assert out["failed"] == out["attempted"]


@pytest.mark.parametrize("cell", BENCH_CELLS)
def test_every_cell_loads_and_builds_its_specs(cell):
    """Each cell's configuration states only calibration the program
    runs, and a request becomes one spec per architecture and lane with
    the file's fleet, messages, tenants, workload and params.  Nothing
    is run: the larger cells take seconds a request on the CPU."""
    c = run.load_cell(str(ROOT), cell)
    cfg = c.cfg
    assert run.calibration_gaps(cfg) == []
    request = generator.Traffic(c.mix, cfg["architectures"],
                                2 ** 33 + 7).request(0)
    specs = run.Client(cfg).specs(request)
    assert len(specs) == len(cfg["architectures"]) * c.mix["lanes"]
    assert [(s.arch, s.params.seed) for s in specs] == [
        (arch, seed) for arch, seeds in request for seed in seeds]
    for s in specs:
        assert (s.pattern, s.n_producers, s.n_consumers, s.total_messages,
                s.tenants, s.tenant_isolation) == (
            cfg["pattern"], cfg["n_producers"], cfg["n_consumers"],
            cfg["total_messages"], cfg["tenants"], cfg["tenant_isolation"])
        assert (s.params.engine, s.params.jax_device_loop) == ("jax", True)
        for key, value in cfg["params"].items():
            assert getattr(s.params, key) == value, key
        wl = dict(cfg["workload"])
        assert s.workload.name == wl.pop("name")
        for key, value in wl.items():
            assert getattr(s.workload, key) == value, key


def test_unknown_workload_is_refused(tmp_path):
    root = make_checkout(tmp_path)
    with pytest.raises(SystemExit):
        run.load_cell(str(root), CELL + "x")
