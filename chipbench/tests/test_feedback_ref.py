"""The feedback cell's plain reference (``chipbench/refs/feedback.py``)
against the device program, on a 32 x 32 feedback configuration (the
smallest fleet the device program takes feedback on) on the CPU: a
rehearsal run is correct, the program's consume, publish and
round-trip times agree with the reference within its limits, and the
float32 control does not."""

from __future__ import annotations

import json

import numpy as np
import pytest

from _tiny import ROOT, args, make_checkout, stub_chip

from chipbench import check, control, generator, rtt_gap, run, trace


def fb_config() -> dict:
    cfg = json.loads(
        (ROOT / "chipbench/configs/fb-fig6-c64.json").read_text())
    cfg.update(name="fb-tiny", n_producers=32, n_consumers=32,
               total_messages=1024)
    return cfg


def fb_checkout(tmp):
    """The tiny cell of ``_tiny.make_checkout``, its configuration the
    32 x 32 feedback one."""
    root = make_checkout(tmp)
    (root / "chipbench/configs/ws-tiny.json").write_text(
        json.dumps(fb_config()))
    return root


@pytest.fixture(scope="module")
def ref():
    return check.load_reference(str(ROOT), "feedback")


def test_rehearsal_run_is_correct(tmp_path, monkeypatch):
    root = fb_checkout(tmp_path)
    stub_chip(monkeypatch)
    out = run.run(args(seed=2 ** 32 + 5, seconds=0.1), root=str(root))
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["checks"]) == set(check.load_reference(
        str(root), "feedback").LIMITS) | {
            "off_device_path", "lost_messages", "duplicate_messages"}


def test_traced_rehearsal_reads_the_reply_layer(tmp_path, monkeypatch):
    from repro.core import jax_device_loop as dl
    root = fb_checkout(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"].startswith("reply_"):
            m["workloads"].append("tiny.paper3")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    stub_chip(monkeypatch)
    for name in run.SPANS:      # the run wraps these; restored afterwards
        monkeypatch.setattr(dl, name, getattr(dl, name))
    real = trace.read_xplane

    def read(path, names):      # the CPU trace has no device plane
        tr = real(path, names)
        tr.ops = [(0, "fusion", s + (e - s) // 4, e - (e - s) // 4)
                  for n, s, e in tr.annotations if n == trace.REQUEST]
        tr.n_devices = 1
        return tr
    monkeypatch.setattr(trace, "read_xplane", read)
    monkeypatch.setattr(run, "device_check",
                        lambda chips: ("cpu", "TPU v5 lite", 1))
    out = run.run(args(seconds=0.5, trace=1), root=str(root))
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert 0 < m["reply_drain_pct"]["value"] < 100
    assert 0 < m["reply_buffer_fill_pct"]["value"] <= 100
    assert m["scan_hbm_roofline_pct"]["value"] > 0


def test_program_agrees_with_the_reference_and_the_control_does_not(ref):
    cfg = fb_config()
    traffic = generator.Traffic({"lanes": 3}, cfg["architectures"],
                                2 ** 31 + 11)
    client = run.Client(cfg)
    request = traffic.request(0)
    results, summaries = client.send(client.specs(request))
    program = check.sample_gaps(cfg, ref, [
        (request, results, [s.throughput_msgs_s for s in summaries])])
    assert check.verdict(program, ref.LIMITS)[0], program
    ctl = check.sample_gaps(cfg, ref, [control.control_item(cfg, ref,
                                                            request)])
    ok, checks = check.verdict(ctl, ref.LIMITS)
    assert not ok, checks
    assert ctl["consume_rel_gap"] > 10 * ref.LIMITS["consume_rel_gap"]
    # round-trip times, which ``correct`` does not read
    limit = ref.LIMITS["consume_rel_gap"]
    i = 0
    for arch, seeds in request:
        want = ref.simulate(cfg, arch, seeds)
        assert np.isfinite(want["rtt"]).all() and want["peak"] > 0
        got = results[i:i + len(seeds)]
        assert rtt_gap.rtt_rel_gap(got, want) < limit
        f32 = ref.simulate(cfg, arch, seeds, np.float32)
        assert rtt_gap.rtt_rel_gap(
            [_Rtts(f32["rtt"][:, k].astype(np.float64))
             for k in range(len(seeds))], want) > 10 * limit
        # a reply lost reads as a gap, not as agreement
        short = [_Rtts(r.rtts[1:]) for r in got]
        assert rtt_gap.rtt_rel_gap(short, want) == float("inf")
        i += len(seeds)


class _Rtts:
    def __init__(self, rtts):
        self.rtts = rtts


def test_hops_count_all_four_legs(ref):
    ws = check.load_reference(str(ROOT), "work_sharing")
    cfg = fb_config()
    for arch in cfg["architectures"]:
        both = ref.hops_per_message(cfg, arch)
        request_legs = ws.hops_per_message(dict(cfg, pattern="work_sharing"),
                                           arch)
        assert both > 1.5 * request_legs
