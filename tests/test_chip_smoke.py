"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and all
of its phases pass end to end at small sizes when the platform check is
stubbed here — the rehearsal of the chip run."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import chip_smoke  # noqa: E402


def test_chip_smoke_refuses_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main()
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_chip_smoke_phases_pass_at_small_size(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "device_check",
                        lambda: ("cpu", "cpu", 1))
    monkeypatch.setattr(chip_smoke, "compile_cache", lambda: "off")
    monkeypatch.setattr(chip_smoke, "GRID", dict(
        archs=("dts", "prs-haproxy", "mss"), clients=4, tenants=4,
        messages=512))
    monkeypatch.setattr(chip_smoke, "FLEET", dict(
        archs=("dts",), clients=16, tenants=1, messages=2048))
    monkeypatch.setattr(chip_smoke, "COHORT", dict(
        arch="mss", consumers=8, messages=400))
    chip_smoke.main()
    out = capsys.readouterr().out.splitlines()
    assert json.loads(out[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu",
                               "count": 1}}
    seeds = len(chip_smoke.SEEDS)
    assert sum("path=jax-device" in line for line in out) == 4 * seeds
    assert sum("path=vectorized" in line for line in out) == 1
    assert any("NumPy-mirror oracle" in line for line in out)
