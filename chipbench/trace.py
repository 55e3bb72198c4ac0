"""Reduction of a profiler trace to device busy time and idle gaps.

The traced run writes an ``.xplane.pb`` with ``jax.profiler``; this
module reads it with ``jax.profiler.ProfileData`` and reduces it:

* device busy: the union of the intervals in which an operation ran on
  a device (the ``XLA Ops`` line of each ``/device:`` plane), inside
  the traced window;
* idle gaps: the holes in that union, each labelled with the innermost
  layer annotation the host had open at the gap's midpoint, or
  ``other host``;
* device time per operation name, for the breakdown.

The traced window is the span of the host's request annotations
(``REQUEST``).  Times are in seconds.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import os
from typing import Optional

REQUEST = "chipbench.request"
OTHER = "other host"


@dataclasses.dataclass
class Trace:
    """Device operations and host annotations, in nanoseconds."""

    ops: list            # [(device, name, start_ns, end_ns)]
    annotations: list    # [(name, start_ns, end_ns)] host annotations
    n_devices: int


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def read_xplane(path: str, names: tuple) -> Trace:
    """Device ops and the host annotations whose name is in ``names``
    (plus ``REQUEST``) from one ``.xplane.pb`` (gzipped if ``.gz``)."""
    from jax.profiler import ProfileData
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    ops, anns, n_dev = [], [], 0
    keep = set(names) | {REQUEST}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            line = lines.get("XLA Ops")
            if line is None:
                continue
            ops += [(n_dev, op_name(e.name), e.start_ns,
                     e.start_ns + e.duration_ns) for e in line.events]
            n_dev += 1
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                anns += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.name in keep]
    return Trace(ops=ops, annotations=anns, n_devices=n_dev)


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(found)}")
    return found[0]


def union(intervals: list) -> list:
    """Merged, sorted ``[(start, end)]`` of possibly overlapping ones."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: list, lo: float, hi: float) -> list:
    """Holes of a merged interval list inside ``[lo, hi]``."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def label(gap: tuple, annotations: list) -> str:
    """The innermost (shortest) annotation open at the gap's midpoint."""
    mid = 0.5 * (gap[0] + gap[1])
    open_ = [(e - s, n) for n, s, e in annotations if s <= mid < e]
    return min(open_)[1] if open_ else OTHER


@dataclasses.dataclass
class Reduced:
    busy_s: float        # mean over devices of the busy union
    window_s: float
    n_requests: int      # request annotations inside the window
    device_ops: list     # [[name, seconds]], most time first
    idle_gaps: list      # [[label, seconds]], longest first


def reduce(tr: Trace, top: int = 10) -> Optional[Reduced]:
    """Busy time, window and breakdown of a trace; ``None`` when it
    holds no request annotation or no device operation."""
    reqs = [(s, e) for n, s, e in tr.annotations if n == REQUEST]
    if not reqs or not tr.ops or not tr.n_devices:
        return None
    lo, hi = min(s for s, _ in reqs), max(e for _, e in reqs)
    per_dev = [union(clip([(s, e) for d, _, s, e in tr.ops if d == dev],
                          lo, hi)) for dev in range(tr.n_devices)]
    busy_ns = sum(e - s for b in per_dev for s, e in b) / tr.n_devices
    # idle gaps are those of the first device (all of them, on one chip)
    busy = per_dev[0]
    per_op: dict = {}
    for _, n, s, e in tr.ops:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            per_op[n] = per_op.get(n, 0.0) + d
    layer_anns = [a for a in tr.annotations if a[0] != REQUEST]
    holes = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return Reduced(
        busy_s=busy_ns * 1e-9, window_s=(hi - lo) * 1e-9,
        n_requests=len(reqs),
        device_ops=[[n, t * 1e-9] for n, t in sorted(
            per_op.items(), key=lambda x: -x[1])[:top]],
        idle_gaps=[[label(g, layer_anns), (g[1] - g[0]) * 1e-9]
                   for g in holes])
