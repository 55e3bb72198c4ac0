"""Whether the timed requests produced the right answers.

Two kinds of number are compared, each against a limit:

* over every request of the window: results not on the whole-run device
  program (``RunResult.path != "jax-device"``), and, producer by
  producer, messages published but not consumed and messages consumed
  more than once (the configuration's guarantee: each consumed exactly
  once).  All three have the limit 0;
* over a sample of the window's requests drawn from ``--seed``: each
  result's per-message consume times and publish starts, as a share of
  the result's simulated span, and its summary throughput, against the
  plain reference of the configuration's pattern
  (``chipbench/refs/<pattern>.py``), which sets those limits.  Rounding
  in a clock grows with the clock, so a gap in seconds would judge a
  long simulation by a short one's limit.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np

DEVICE_PATH = "jax-device"


def load_reference(root: str, pattern: str):
    path = os.path.join(root, "chipbench", "refs", f"{pattern}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no plain reference for pattern "
                                f"{pattern!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_ref_{pattern}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def window_faults(cfg: dict, results: list) -> tuple[int, int, int]:
    """(results off the device path, messages lost, messages consumed
    twice) of one request.  Each producer publishes ``total_messages //
    n_producers``; a producer's shortfall counts as lost and its excess
    as duplicated, so neither cancels the other."""
    n_p = cfg["n_producers"]
    want = cfg["total_messages"] // n_p
    off = lost = dup = 0
    for r in results:
        off += r.path != DEVICE_PATH
        got = np.bincount(np.asarray(r.consume_producers, dtype=np.int64),
                          minlength=n_p)
        if got.size > n_p:      # a producer that does not exist
            dup += int(got[n_p:].sum())
            got = got[:n_p]
        lost += int(np.maximum(want - got, 0).sum())
        dup += int(np.maximum(got - want, 0).sum())
        # consume times without a producer, or producers without a time
        unattributed = r.n_consumed - r.consume_producers.size
        dup += max(unattributed, 0)
        lost += max(-unattributed, 0)
    return off, lost, dup


def _rel_gap(got: np.ndarray, want: np.ndarray, span: float) -> float:
    """Widest gap of ``got`` to ``want`` as a share of ``span``; a shape
    that differs, or any value not finite, reads ``inf``."""
    if got.shape != want.shape:
        return float("inf")
    d = np.abs(np.asarray(got, dtype=np.float64) - want) / span
    return float(d.max()) if np.isfinite(d).all() else float("inf")


def sample_gaps(cfg: dict, ref, sample: list) -> dict:
    """Widest gaps to the reference over ``[(request, results,
    throughputs)]``: ``request`` is ``[(arch, seeds)]``, ``results``
    the program's results in the same order (architecture-major, one per
    seed) and ``throughputs`` their summary throughputs.  A per-message
    gap is taken as a share of its result's span, the reference's last
    consume time in that lane."""
    consume = publish = thr = 0.0
    for request, results, throughputs in sample:
        i = 0
        for arch, seeds in request:
            want = ref.simulate(cfg, arch, seeds)
            for lane in range(len(seeds)):
                r = results[i]
                wc = want["consume"][:, lane]
                span = float(wc.max())
                consume = max(consume, _rel_gap(r.consume_times, wc, span))
                publish = max(publish, _rel_gap(
                    r.publish_starts, np.sort(want["publish"][:, lane]),
                    span))
                t_ref = ref.throughput(wc)
                rel = abs(throughputs[i] - t_ref) / t_ref
                thr = max(thr, rel if np.isfinite(rel) else float("inf"))
                i += 1
    return {"consume_rel_gap": consume, "publish_rel_gap": publish,
            "throughput_rel_gap": thr}


def verdict(readings: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: [value, limit]})``; a NaN fails."""
    checks = {k: [readings[k], limits[k]] for k in limits}
    ok = all(v <= lim for v, lim in checks.values())
    return ok, checks
