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


#: every (pattern, arch) pair the regime gate admits, each at the small
#: shape the device tests use: work sharing on a 4 x 2 fleet with 64
#: msgs, feedback on the smallest fleet it takes, 32 x 32 with 32
#: msgs/producer under a window that never binds
DEVICE_CELLS = [(pattern, arch) for pattern in ("work_sharing", "feedback")
                for arch in ("dts", "prs-haproxy", "mss")]


@pytest.mark.parametrize("pattern,arch", DEVICE_CELLS)
def test_device_program_compiles_for_v5e(pattern, arch, one_chip):
    """The whole-run device program at a small cell the regime gate
    admits, for each pattern and architecture it takes; the feedback
    cells carry all four legs (publish, delivery, reply-publish,
    reply-delivery), both pumps and the pending-reply buffer."""
    fb = pattern == "feedback"
    n_producers, n_consumers = (32, 32) if fb else (4, 2)
    spec = ExperimentSpec(
        pattern=pattern, workload=get_workload("dstream"), arch=arch,
        n_producers=n_producers, n_consumers=n_consumers,
        total_messages=1024 if fb else 64,
        params=SimParams(seed=0, confirm_window=128 if fb else 32))
    sim = VectorizedStreamSim(spec)
    ok, why = dl._device_loop_ok(sim)
    assert ok, why
    ws = dl.build_static(sim)
    if fb:
        assert ws.meta["S_rp"] and ws.meta["S_rd"]
    assert bool(ws.meta["feedback"]) == fb
    fn = dl._compiled_program(ws.signature(), dl._meta_key(ws.meta), False)
    compiled = _compile(fn, (ws.xs, ws.inv, dl.draw_jitter(sim, ws)),
                        one_chip)
    mem = compiled.memory_analysis()
    # one v5e chip holds 16 GB; the program must leave room for the rest
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 2**30
