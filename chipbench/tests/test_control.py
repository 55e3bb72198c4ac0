"""The comparison that decides ``correct`` fails what it must: the
float32 control in the program's place, and a run whose timed path is
broken underneath in each way this cell can break."""

from __future__ import annotations

import numpy as np
import pytest

from _tiny import ROOT, args, make_checkout, stub_chip, tiny_config

from chipbench import check, control, generator, run


@pytest.fixture(scope="module")
def ref():
    return check.load_reference(str(ROOT), "work_sharing")


@pytest.mark.parametrize("messages", [256, 1024])
def test_control_fails_and_program_passes(ref, messages):
    """At 1024 messages each producer publishes twice its confirm window,
    so publish starts wait on confirms and their gap is read too."""
    cfg = dict(tiny_config(), total_messages=messages)
    traffic = generator.Traffic({"lanes": 3},
                                cfg["architectures"], 2 ** 31 + 11)
    client = run.Client(cfg)
    request = traffic.request(0)
    results, summaries = client.send(client.specs(request))
    program = check.sample_gaps(cfg, ref, [
        (request, results, [s.throughput_msgs_s for s in summaries])])
    ctl = check.sample_gaps(cfg, ref, [control.control_item(cfg, ref,
                                                            request)])
    assert check.verdict(program, ref.LIMITS)[0], program
    ok, checks = check.verdict(ctl, ref.LIMITS)
    assert not ok, checks
    assert ctl["consume_rel_gap"] > 10 * ref.LIMITS["consume_rel_gap"]
    window = cfg["params"]["confirm_window"]
    if messages > window * cfg["n_producers"]:
        assert program["publish_rel_gap"] > 0, program
        assert ctl["publish_rel_gap"] > 10 * ref.LIMITS["publish_rel_gap"]


@pytest.fixture
def fresh_programs():
    from repro.core import jax_device_loop as dl
    dl._compiled_program.cache_clear()
    yield dl
    dl._compiled_program.cache_clear()


def _run(tmp_path, monkeypatch) -> dict:
    root = make_checkout(tmp_path)
    stub_chip(monkeypatch)
    return run.run(args(seconds=0.1), root=str(root))


def test_sound_run_is_correct(tmp_path, monkeypatch):
    assert _run(tmp_path, monkeypatch)["correct"]


def test_step_that_returns_its_state_unchanged(tmp_path, monkeypatch,
                                               fresh_programs):
    dl = fresh_programs
    step = dl._wave_step

    def stuck(ops, meta, inv, carry, x):
        _, ys = step(ops, meta, inv, carry, x)
        return carry, ys
    monkeypatch.setattr(dl, "_wave_step", stuck)
    out = _run(tmp_path, monkeypatch)
    assert not out["correct"]
    assert out["checks"]["consume_rel_gap"][0] > 1e-6


def test_half_of_each_batch_left_out(tmp_path, monkeypatch):
    from repro.core import jax_device_loop as dl
    assemble = dl._assemble

    def half(sim, ws, ys):
        out = []
        for r in assemble(sim, ws, ys):
            keep = r.consume_times.size // 2
            r.consume_times = r.consume_times[:keep]
            r.consume_producers = r.consume_producers[:keep]
            out.append(r)
        return out
    monkeypatch.setattr(dl, "_assemble", half)
    out = _run(tmp_path, monkeypatch)
    assert not out["correct"]
    assert out["checks"]["lost_messages"][0] > 0


def test_one_message_consumed_twice(tmp_path, monkeypatch):
    from repro.core import jax_device_loop as dl
    assemble = dl._assemble

    def twice(sim, ws, ys):
        out = assemble(sim, ws, ys)
        r = out[0]
        r.consume_times = np.append(r.consume_times, r.consume_times[-1])
        r.consume_producers = np.append(r.consume_producers,
                                        r.consume_producers[-1])
        return out
    monkeypatch.setattr(dl, "_assemble", twice)
    out = _run(tmp_path, monkeypatch)
    assert not out["correct"]
    assert out["checks"]["duplicate_messages"][0] > 0
    assert out["checks"]["lost_messages"][0] == 0


def test_one_message_lost_and_another_doubled_do_not_cancel(tmp_path,
                                                             monkeypatch):
    from repro.core import jax_device_loop as dl
    assemble = dl._assemble

    def swapped(sim, ws, ys):
        out = assemble(sim, ws, ys)
        r = out[0]
        other = np.flatnonzero(r.consume_producers != r.consume_producers[0])
        r.consume_producers = r.consume_producers.copy()
        r.consume_producers[other[0]] = r.consume_producers[0]
        return out
    monkeypatch.setattr(dl, "_assemble", swapped)
    out = _run(tmp_path, monkeypatch)
    assert not out["correct"]
    dup, lost = (out["checks"][k][0] for k in ("duplicate_messages",
                                                "lost_messages"))
    assert dup == lost > 0


def test_one_answer_altered_where_it_is_produced(tmp_path, monkeypatch):
    from repro.core import jax_device_loop as dl
    program = dl._run_program

    def altered(fn, *a):
        ys = program(fn, *a)
        seen = ys["seen"].copy()
        live = np.where(np.isfinite(seen), seen, -np.inf)
        seen.flat[int(np.argmax(live))] += 1e-8
        return dict(ys, seen=seen)
    monkeypatch.setattr(dl, "_run_program", altered)
    out = _run(tmp_path, monkeypatch)
    assert not out["correct"]
    assert out["checks"]["consume_rel_gap"][0] > out["checks"][
        "consume_rel_gap"][1]
