"""A checkout in a temporary directory whose benchmark holds one tiny
cell, made of files dropped into the data directories only: the
rehearsal of a run on the CPU, with the chip check stubbed."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "tiny.paper3"


def tiny_config(edit: tuple = ()) -> dict:
    """A 4 x 4 work-sharing configuration; ``edit`` is ``(group, key,
    value)``, which sets one value of a group such as ``inventory``."""
    cfg = json.loads((ROOT / "chipbench/configs/ws-fig4-c64.json").read_text())
    cfg.update(name="ws-tiny", n_producers=4, n_consumers=4,
               total_messages=256)
    if edit:
        group, key, value = edit
        cfg[group][key] = value
    return cfg


def make_checkout(tmp: Path, extra_layer: str = "", edit: tuple = ()) -> Path:
    """``tmp`` with the repository's BENCHMARK.json plus one new cell,
    configuration (``tiny_config(edit)``) and traffic mix, the
    benchmark's readers and references, and optionally one more
    per-layer reader."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "ws-tiny", "source": "test", "reduced": [], "why": "test",
        "file": "chipbench/configs/ws-tiny.json"})
    bench["workloads"].append({
        "name": CELL, "config": "ws-tiny", "traffic": "tiny3", "chips": 1,
        "why": "test"})
    d = tmp / "chipbench"
    for sub in ("layers", "refs", "configs", "traffic"):
        shutil.copytree(ROOT / "chipbench" / sub, d / sub)
    (d / "configs/ws-tiny.json").write_text(json.dumps(tiny_config(edit)))
    (d / "traffic/tiny3.json").write_text(json.dumps(
        {"lanes": 3, "why": "test"}))
    if extra_layer:
        bench["per_layer"].append({
            "name": extra_layer, "unit": "ms", "better": "lower",
            "source": "program_span", "layer": "device program",
            "moves": "sim_msgs_per_s", "workloads": [CELL]})
        (d / f"layers/{extra_layer}.py").write_text(
            "def read(obs):\n"
            "    rows = [s['_run_program'] for s in obs.spans if s]\n"
            "    return 1e3 * sum(rows) / len(rows)\n")
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def stub_chip(monkeypatch) -> None:
    """Run on the CPU: the chip check passes, no persistent cache."""
    from chipbench import run
    monkeypatch.setattr(run, "device_check", lambda chips: ("cpu", "cpu", 1))
    monkeypatch.setattr(run, "compile_cache", lambda root: "off")


def args(seed: int = 5, seconds: float = 1.0, trace: int = 0) -> list:
    return ["--workload", CELL, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
