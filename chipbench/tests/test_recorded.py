"""The readers of the program's own spans and counters: the window's
part of a recording, and a traced run on the CPU with and without the
program's recorder."""

from __future__ import annotations

import pytest

from _tiny import args, make_checkout, stub_chip

from chipbench import recorded, run, trace

#: metrics read from the program's own recorder
RECORDED = ("engine_build_ms", "engines_per_request", "summarize_ms",
            "dispatch_ms", "device_wait_ms", "fetch_ms", "transfer_kb",
            "pad_waste_pct")
#: metrics the benchmark had before the recorder
WRAPPED = ("entry_host_ms", "static_build_ms", "assemble_ms",
           "device_busy_ms", "device_idle_pct", "scan_hbm_roofline_pct")


def test_window_is_the_last_requests_with_what_follows_them():
    from repro.core import spans
    with spans.recording() as rec:
        for k in range(3):
            with spans.span(recorded.RUN_MANY):
                spans.count("streamsim.engines", k + 1)
                with spans.span("streamsim.program"):
                    pass
            with spans.span("streamsim.summarize"):
                spans.count("streamsim.engines", 10)
    w = recorded.of_requests(rec, 2)
    assert w.n == 2 and len(w.per_request) == 2
    assert w.counters == {"streamsim.engines": 2 + 10 + 3 + 10}
    ns = {}
    for s in rec.spans[3:]:
        ns[s.name] = ns.get(s.name, 0) + s.end_ns - s.start_ns
    assert w.total_ns == ns
    per = rec.requests()
    assert w.per_request[1] == {
        n: per[4].get(n, 0) + per[5].get(n, 0) for n in set(per[4]) | set(
            per[5])}
    assert recorded.of_requests(rec, 4) is None
    assert recorded.of_requests(rec, 0) is None


def test_start_leaves_a_recording_it_does_not_hold():
    from repro.core import spans
    with spans.recording() as rec:
        recorded.start()
        with spans.span(recorded.RUN_MANY):
            pass
        assert recorded._state["rec"] is None
    assert len(rec.spans) == 1
    recorded.start()
    assert spans.on()
    recorded.stop()
    assert not spans.on()


def test_readers_on_the_chip_fixture():
    """The recording of two tiny requests on the chip (the fixture of
    ``test_program_trace.py``), each a ``run_many`` root as in the
    benchmark's run once the fixture's own request spans are dropped."""
    import json
    from types import SimpleNamespace

    from _tiny import ROOT
    from repro.core import spans
    saved = json.loads(
        (ROOT / "chipbench/tests/data/scoped.json").read_text())
    kept = [i for i, s in enumerate(saved["spans"]) if s[0] != trace.REQUEST]
    new = {old: k for k, old in enumerate(kept)}
    rec = spans.Record()
    for i in kept:
        name, t0, t1, parent, _ = saved["spans"][i]
        parent = new.get(parent, -1)
        if parent < 0:
            rec.n_requests += 1
        request = rec.spans[parent].request if parent >= 0 else (
            rec.n_requests - 1)
        rec.spans.append(spans.Span(name, t0, t1, parent, request))
    runs = [s.request for s in rec.spans
            if s.parent < 0 and s.name == recorded.RUN_MANY]
    assert len(runs) == 2
    # the fixture keeps the totals; the two requests counted alike
    rec.request_counters = {r: {k: v // 2 for k, v in
                                saved["counters"].items()} for r in runs}
    readers = {name: run.load_reader(str(ROOT), name) for name in RECORDED}
    recorded.stop()
    recorded._state.update(rec=rec, window=None)
    obs = SimpleNamespace(walls=[0.01, 0.01], spans=[{}, {}])
    got = {name: read(obs) for name, read in readers.items()}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["engines_per_request"] == 2          # probe + run, 1 group
    assert got["transfer_kb"] == pytest.approx((31408 + 7680) / 1024 / 2)
    assert got["pad_waste_pct"] == pytest.approx(100 * (1 - 128 / 192))
    # the three phases of a program run add up to it, less its own time
    per = {n: recorded.span_ms(obs, n) for n in (
        "streamsim.program", "streamsim.dispatch", "streamsim.wait",
        "streamsim.fetch")}
    parts = sum(v for n, v in per.items() if n != "streamsim.program")
    assert 0 < per["streamsim.program"] - parts < 0.5


def traced_cpu_run(tmp_path, monkeypatch) -> dict:
    """A traced run of the tiny cell on the CPU, whose trace has no
    device plane: one busy interval per request annotation stands in."""
    from repro.core import jax_device_loop as dl
    root = make_checkout(tmp_path)
    stub_chip(monkeypatch)
    for name in run.SPANS:      # the run wraps these; restored afterwards
        monkeypatch.setattr(dl, name, getattr(dl, name))
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
    return run.run(args(seconds=1.0, trace=1), root=str(root))


def test_traced_run_reports_the_program_spans(tmp_path, monkeypatch,
                                              capsys):
    from repro.core import spans
    out = traced_cpu_run(tmp_path, monkeypatch)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    for name in RECORDED + WRAPPED:
        assert m[name]["value"] > 0, name
    assert m["engines_per_request"]["value"] == 6   # 3 archs, probe + run
    assert 0 < m["pad_waste_pct"]["value"] < 100
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    # the recorder is off once the readers have read
    assert not spans.on()
    err = capsys.readouterr().err
    assert "agreement _run_program" in err
    assert "program counters over" in err


def test_traced_run_of_a_program_without_spans(tmp_path, monkeypatch):
    """A program that has no recorder (as before it had one) still gives
    a traced run; the metrics that read the recorder are left out."""
    monkeypatch.setattr(recorded, "recorder", lambda: None)
    out = traced_cpu_run(tmp_path, monkeypatch)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert not set(RECORDED) & set(m)
    for name in WRAPPED:
        assert m[name]["value"] > 0, name


def test_untraced_run_records_nothing(tmp_path, monkeypatch):
    from repro.core import spans
    root = make_checkout(tmp_path)
    stub_chip(monkeypatch)
    out = run.run(args(seconds=0.5), root=str(root))
    assert out["correct"], out["checks"]
    assert not spans.on()
    assert not set(RECORDED) & set(out["metrics"])


@pytest.fixture(autouse=True)
def _no_recording_left_open():
    yield
    recorded.stop()
