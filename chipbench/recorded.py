"""The program's own spans and counters over a traced run's window.

The traced run (``--trace 1``) loads its per-layer readers before
set-up.  The readers of the program's spans and counters call
:func:`start` when they load, which turns the program's recorder
(``repro.core.spans``) on for the rest of the run: set-up, the
profiled requests and the window.  The first of them to read calls
:func:`window`, which turns the recorder off again and keeps the
window's part of the recording: the window's requests are the last
``len(obs.walls)`` calls of ``run_many``, each with the spans and
counts recorded from its start to the start of the next (its
``summarize`` calls included).  That reader also prints, on standard
error, each slow request's span self times beside the window's
medians, the window's counters, and the agreement of the program's
spans with the benchmark's wrappers of the same functions.

A program without ``repro.core.spans`` records nothing: :func:`window`
is then ``None`` and the readers leave their metrics out.
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Any, Optional

import numpy as np

#: the root span of one ``run_many`` call
RUN_MANY = "streamsim.run_many"
#: a request over this many times the median wall time is slow
STALL = 1.25

_state: dict = {"cm": None, "rec": None, "window": None}


def recorder() -> Any:
    """The program's span recorder, ``repro.core.spans``; ``None`` for a
    program that has none."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    return spans


def start() -> None:
    """Record the program's spans from now on, in place of the
    recording an earlier :func:`start` opened.  Leaves a recording that
    someone else holds open as it is, and records nothing then."""
    stop()
    _state.update(rec=None, window=None)
    spans = recorder()
    if spans is None or spans.on():
        return
    cm = spans.recording()
    _state.update(cm=cm, rec=cm.__enter__())


def stop() -> None:
    """Close the recording :func:`start` opened, if it is open."""
    cm = _state["cm"]
    _state["cm"] = None
    if cm is not None:
        cm.__exit__(None, None, None)


@dataclasses.dataclass
class Window:
    """The recording of the window's ``n`` requests."""

    n: int
    total_ns: dict       # {span name: ns summed over the window}
    counters: dict       # {counter: total over the window}
    per_request: list    # per request, {span name: self ns}


def of_requests(rec: Any, n: int) -> Optional[Window]:
    """The last ``n`` ``run_many`` calls of a recording, each with what
    was recorded up to the next; ``None`` if it holds fewer."""
    starts = [i for i, s in enumerate(rec.spans)
              if s.parent < 0 and s.name == RUN_MANY]
    if n <= 0 or len(starts) < n:
        return None
    starts = starts[-n:] + [len(rec.spans)]
    self_ns = rec.requests()
    counts = rec.request_counters
    total: dict = {}
    counters: dict = {}
    per_request = []
    for lo, hi in zip(starts, starts[1:]):
        own: dict = {}
        for s in rec.spans[lo:hi]:
            total[s.name] = total.get(s.name, 0) + s.end_ns - s.start_ns
            if s.parent < 0:
                for name, d in self_ns[s.request].items():
                    own[name] = own.get(name, 0) + d
                for name, c in counts.get(s.request, {}).items():
                    counters[name] = counters.get(name, 0) + c
        per_request.append(own)
    return Window(n=n, total_ns=total, counters=counters,
                  per_request=per_request)


def window(obs: Any) -> Optional[Window]:
    """The window's recording (ends the recording on the first call of
    a run), or ``None`` where the program recorded nothing."""
    rec = _state["rec"]
    if rec is None:
        return None
    if _state["window"] is None:
        stop()
        _state["window"] = of_requests(rec, len(obs.walls))
        if _state["window"] is not None:
            say_slow_spans(obs.walls, _state["window"])
            say_agreement(obs.spans, _state["window"])
    return _state["window"]


def span_ms(obs: Any, name: str) -> Optional[float]:
    """Mean ms per window request of the program's spans ``name``."""
    w = window(obs)
    if w is None or name not in w.total_ns:
        return None
    return 1e-6 * w.total_ns[name] / w.n


def counter(obs: Any, name: str) -> Optional[float]:
    """Mean per window request of the program's counter ``name``."""
    w = window(obs)
    if w is None or name not in w.counters:
        return None
    return w.counters[name] / w.n


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def say_slow_spans(walls: list, w: Window) -> None:
    """For the requests over ``STALL`` times the median wall time, each
    program span's self time (ms) beside its median over the window;
    then the window's counters."""
    med = float(np.median(walls))
    names = sorted({n for d in w.per_request for n in d})
    median = {n: 1e-6 * float(np.median([d.get(n, 0)
                                         for d in w.per_request]))
              for n in names}
    say(f"program span self ms, median of the window: {median!r}")
    for i, wall in enumerate(walls):
        if wall > STALL * med:
            own = {n: v * 1e-6 for n, v in w.per_request[i].items()}
            top = max(names, key=lambda n: own.get(n, 0.0) - median[n])
            say(f"slow request {i}: wall {wall!r} s; most above its median: "
                f"{top} {own.get(top, 0.0)!r} ms; self ms {own!r}")
    c = w.counters
    say(f"program counters over {w.n} requests: "
        + ", ".join(f"{k} {c.get('streamsim.' + k, 0)}" for k in
                    ("engines", "programs", "cells", "program_builds")))


def say_agreement(span_log: list, w: Window) -> None:
    """Mean ms per request of the benchmark's wrappers of the layer
    functions beside the program's own spans of the same work, and of a
    program run beside its three phases."""
    def wrapped(*names: str) -> float:
        return 1e3 * sum(s.get(n, 0.0) for s in span_log
                         for n in names) / max(len(span_log), 1)

    def own(*names: str) -> float:
        return 1e-6 * sum(w.total_ns.get(n, 0) for n in names) / w.n
    rows = [("build_static + draw_jitter",
             wrapped("build_static", "draw_jitter"),
             own("streamsim.build_static", "streamsim.draw_jitter")),
            ("_run_program", wrapped("_run_program"),
             own("streamsim.program")),
            ("_assemble", wrapped("_assemble"), own("streamsim.assemble")),
            ("program vs dispatch + wait + fetch", own("streamsim.program"),
             own("streamsim.dispatch", "streamsim.wait", "streamsim.fetch"))]
    for name, outer, inner in rows:
        say(f"agreement {name}: {outer!r} ms outside, {inner!r} ms inside, "
            f"difference {outer - inner!r} ms")
