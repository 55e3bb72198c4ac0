"""Ahead-of-time compiles of the StreamSim JAX path for one TPU v5e chip.

The TPU compiler is installed with jaxlib and compiles for a chip that
is described, not attached, so these tests refuse, without a chip, what
the chip's compiler would refuse: a Pallas kernel Mosaic cannot lower,
a 64-bit operand a kernel cannot take, a program that does not fit.
Every compile runs under the engine's scoped x64, as on the chip, with
the persistent compile cache off (a described chip's executables cannot
be read back from it).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and only the test worker
that runs this file needs it.
"""

import jax
import numpy as np
import pytest

from repro.compat import scoped_x64
from repro.core import jax_device_loop as dl
from repro.core import jax_engine
from repro.core.simulator import ExperimentSpec, SimParams
from repro.core.vectorized import VectorizedStreamSim
from repro.core.workloads import get_workload


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        np.shape(a), np.asarray(a).dtype, sharding=sharding), tree)


def _compile(fn, args, sharding):
    with scoped_x64():
        return jax.jit(fn).lower(*_shapes(args, sharding)).compile()


f8, i8 = np.zeros((), np.float64), np.zeros((), np.int64)


def _a(*shape, like=f8):
    return np.zeros(shape, like.dtype)


def _b(*shape):
    return np.zeros(shape, bool)


#: every kernel of jax_engine._kernels() at small pow2 shapes: cohort
#: 16, lanes 4, queues 2, consumers 4
KERNEL_ARGS = {
    "fifo_scan_1d": (_a(16), _a(16), f8),
    "fifo_scan_lanes": (_a(16, 4), _a(16, 4), _a(4)),
    "fifo_scan_cells": (_a(4, 16, 4), _a(4, 16, 4), _a(4, 4)),
    "pop_until": (_a(16), _b(16), f8),
    "pop_k": (_a(16), _b(16), i8),
    "next_drain": (_a(16), _b(16)),
    "admit_walk": (_a(16), _b(16), _a(2, 8), _a(2, like=i8),
                   _a(2, like=i8), _a(2, like=i8), _a(2, like=i8)),
    "rr_assign": (_a(16, 4), _a(4, like=i8), _a(4, like=i8),
                  _a(4, 8, 4), i8),
    "assign_chunk": (_a(8, 4), _a(8), _b(8), _a(4, 4), _a(4, like=i8),
                     _a(4, like=i8), _a(4, 16, 4), i8),
}


@pytest.mark.parametrize("name", sorted(KERNEL_ARGS))
def test_cohort_kernel_compiles_for_v5e(name, one_chip):
    kernel = getattr(jax_engine._kernels(), name)
    compiled = _compile(kernel.__wrapped__, KERNEL_ARGS[name], one_chip)
    assert "tpu_custom_call" not in compiled.as_text()


def test_device_program_compiles_for_v5e(one_chip):
    """The whole-run device program at a small feedback cell the regime
    gate admits, so all four legs (publish, delivery, reply-publish,
    reply-delivery), both pumps and the pending-reply buffer are in
    it."""
    spec = ExperimentSpec(
        pattern="feedback", workload=get_workload("dstream"), arch="dts",
        n_producers=32, n_consumers=32, total_messages=1024,
        params=SimParams(seed=0, confirm_window=128))
    sim = VectorizedStreamSim(spec)
    ok, why = dl._device_loop_ok(sim)
    assert ok, why
    ws = dl.build_static(sim)
    assert ws.meta["feedback"] and ws.meta["S_rp"] and ws.meta["S_rd"]
    fn = dl._compiled_program(ws.signature(), dl._meta_key(ws.meta), False)
    compiled = _compile(fn, (ws.xs, ws.inv, dl.draw_jitter(sim, ws)),
                        one_chip)
    mem = compiled.memory_analysis()
    # one v5e chip holds 16 GB; the program must leave room for the rest
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 2**30
