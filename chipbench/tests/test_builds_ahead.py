"""The reader of ``builds_ahead``: the program's
``streamsim.builds_ahead`` counter, a mean per window request, and
nothing where the program never counted it."""

from __future__ import annotations

from types import SimpleNamespace

import pytest

from _tiny import ROOT

from chipbench import recorded, run


def _reader():
    read = run.load_reader(str(ROOT), "builds_ahead")
    recorded.stop()
    return read


def _window(rec, n: int) -> SimpleNamespace:
    recorded._state.update(rec=rec, window=None)
    return SimpleNamespace(walls=[0.01] * n, spans=[{}] * n)


def test_reads_the_mean_per_window_request():
    from repro.core import spans
    read = _reader()
    with spans.recording() as rec:
        for ahead in (4, 2, 0):
            with spans.span(recorded.RUN_MANY):
                spans.count("streamsim.builds_ahead", ahead)
    assert read(_window(rec, 2)) == pytest.approx(1.0)


def test_reads_nothing_where_the_program_never_counted_it():
    from repro.core import spans
    read = _reader()
    with spans.recording() as rec:
        for _ in range(2):
            with spans.span(recorded.RUN_MANY):
                spans.count("streamsim.programs_ahead", 2)
    assert read(_window(rec, 2)) is None


def test_reads_what_run_many_counts():
    """The first architecture's program is enqueued before the other
    two are built, so two builds go ahead in each request; one
    architecture builds once, with nothing enqueued."""
    from repro.core import spans
    from repro.core.simulator import ExperimentSpec, SimParams
    from repro.core.vectorized import run_many
    from repro.core.workloads import get_workload

    def specs(archs):
        return [ExperimentSpec(
            pattern="work_sharing", workload=get_workload("dstream"),
            arch=a, n_producers=2, n_consumers=2, total_messages=32,
            params=SimParams(seed=s, engine="jax", jax_device_loop=True))
            for a in archs for s in (1, 2)]
    read = _reader()
    for archs, want in ((("dts", "prs-haproxy", "mss"), 2.0), (("mss",), 0.0)):
        run_many(specs(archs))
        with spans.recording() as rec:
            for _ in range(2):
                run_many(specs(archs))
        assert read(_window(rec, 2)) == want


@pytest.fixture(autouse=True)
def _no_recording_left_open():
    yield
    recorded.stop()
    recorded._state.update(rec=None, window=None)
