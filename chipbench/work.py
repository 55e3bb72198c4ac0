"""The least bytes a request must move, and the chip's peaks.

The count comes from the problem, not from the arrays the program
happens to use, so it reads the same work whatever implements it.  Per
message and lane a simulation must read, in float64, one hold time and
one jitter draw for each resource hop of the message's paths and for its
consumer processing, and write three timestamps: publish start, confirm
and consume.  The hop counts come from the benchmark's own hop graph, in
the plain reference of the configuration's pattern.
"""

from __future__ import annotations

F64 = 8

#: Published peaks, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
#: bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
}


def peak(device_kind: str) -> dict:
    """The peaks of one chip; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks recorded for device kind "
                         f"{device_kind!r}") from None


def least_bytes(cfg: dict, ref, request: list) -> float:
    """Least bytes of one request ``[(arch, seeds), ...]``."""
    total = 0.0
    for arch, seeds in request:
        per_msg = F64 * (2 * (ref.hops_per_message(cfg, arch) + 1) + 3)
        total += cfg["total_messages"] * len(seeds) * per_msg
    return total
