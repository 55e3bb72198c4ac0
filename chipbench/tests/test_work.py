"""The least-bytes count depends only on the configuration and the
traffic, and the peaks table knows only the chips it names."""

from __future__ import annotations

import json

import pytest

from _tiny import ROOT

from chipbench import check, generator, work


def _cfg(name: str) -> dict:
    return json.loads((ROOT / f"chipbench/configs/{name}.json").read_text())


@pytest.fixture(scope="module")
def ref():
    return check.load_reference(str(ROOT), "work_sharing")


def test_count_depends_on_messages_lanes_and_paths_only(ref):
    cfg = _cfg("ws-deploy-16c-t4")
    mix = json.loads((ROOT / "chipbench/traffic/paper3.json").read_text())
    a = generator.Traffic(mix, cfg["architectures"], 1).request(0)
    b = generator.Traffic(mix, cfg["architectures"], 2 ** 33 + 5).request(7)
    assert [s for _, s in a] != [s for _, s in b]
    assert work.least_bytes(cfg, ref, a) == work.least_bytes(cfg, ref, b)
    # linear in lanes and in messages
    one = [(arch, seeds[:1]) for arch, seeds in a]
    assert work.least_bytes(cfg, ref, a) == pytest.approx(
        3 * work.least_bytes(cfg, ref, one))
    double = dict(cfg, total_messages=2 * cfg["total_messages"])
    assert work.least_bytes(double, ref, one) == pytest.approx(
        2 * work.least_bytes(cfg, ref, one))


def test_count_per_message(ref):
    cfg = _cfg("ws-fig4-c64")
    # DTS, one tenant: publish plink, dsn_in, bcpu (+ dsn_int, bcpu when
    # the queue lives on another broker node); delivery mirrors it
    hops = ref.hops_per_message(cfg, "dts")
    assert 6 <= hops <= 10
    got = work.least_bytes(cfg, ref, [("dts", [0])])
    assert got == 4096 * 8 * (2 * (hops + 1) + 3)


def test_unknown_device_kind_raises():
    assert work.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        work.peak("cpu")
