"""The JAX engine (``engine="jax"``): the whole-run device program.

:class:`JaxStreamSim` is the vectorized engine with one more way to
run.  When ``params.jax_device_loop`` requests it and the cell passes
the regime gate (:func:`repro.core.jax_device_loop._device_loop_ok`),
:meth:`~JaxStreamSim.run` and :meth:`~JaxStreamSim.run_stacked` hand
the whole run to :mod:`repro.core.jax_device_loop`: one ``lax.scan``
over generations of messages, recorded as ``RunResult.path ==
"jax-device"``.  Every other cell runs the inherited
:class:`~repro.core.vectorized.VectorizedStreamSim` event loop on the
host, with the same code and results as ``engine="vectorized"`` and
``RunResult.path == "vectorized"``;
:func:`~repro.core.vectorized.run_many` runs such groups as vectorized
groups outright.

The device program's clocks are float64 under
:func:`repro.compat.scoped_x64` (``jax.enable_x64(True)`` as a context,
never the global flag).  On a TPU, which has no native float64, XLA
emulates it.

The module imports without JAX installed; only constructing
:class:`JaxStreamSim` requires it.  :func:`~repro.core.vectorized.run_many`
consults :func:`jax_supported` and runs the cells it refuses on the
vectorized engine.
"""

from __future__ import annotations

from typing import Any

from repro.core.simulator import ENGINES, ExperimentSpec, RunResult
from repro.core.vectorized import VectorizedStreamSim


def jax_available() -> bool:
    """True when jax is installed.  Only a missing package reads as
    unavailable: an installed jax that fails to import raises, rather
    than turning jax cells into quiet vectorized runs."""
    try:
        import jax  # noqa: F401
    except ImportError:
        return False
    return True


def jax_supported(spec: ExperimentSpec) -> tuple[bool, str]:
    """Can the JAX engine take this cell?  Returns ``(ok, reason)``.

    Every cell shape the vectorized engine accepts is supported except
    chaos cells (``params.chaos``): the device program has no outage
    epochs and its regime gate does not look for them, so chaos cells
    run on the vectorized engine.  ``run_many`` records the fallback per
    cell (the result's ``spec.params.engine``)."""
    if not jax_available():
        return False, "jax is not importable in this environment"
    if spec.params.chaos is not None:
        return False, ("chaos schedules mutate topology mid-run; the "
                       "device program has no outage epochs — falling "
                       "back to the vectorized engine")
    return True, ""


class JaxStreamSim(VectorizedStreamSim):
    """The vectorized engine, or the whole-run device program where the
    cell asks for it and the regime gate admits it.

    Same constructor/run/stacking contract as the base class.  Off the
    device program it *is* the vectorized engine, bit for bit; on it,
    parity against the heap engine is held at the ``device_loop.*``
    bands of :mod:`repro.core.parity`.
    """

    #: stacked seed-lanes per device program (the vectorized engine's
    #: run_many chunks at vectorized.STACK_MAX_LANES)
    STACK_MAX_LANES = 64

    def __init__(self, *args: Any, **kwargs: Any) -> None:
        if not jax_available():
            raise ImportError(
                "engine='jax' requires jax; install jax or use "
                "engine='vectorized' (run_many falls back automatically)")
        spec = args[0] if args else kwargs.get("spec")
        if spec is not None and spec.params.chaos is not None:
            raise ValueError(
                "engine='jax' does not support chaos schedules (the "
                "device program has no outage epochs); use "
                "engine='vectorized' (run_many falls back automatically)")
        super().__init__(*args, **kwargs)
        #: why this cell does not run on the whole-run device program
        #: ("" when it does); results record the path as ``RunResult.path``
        self.device_loop_why = "jax_device_loop not requested"
        if self.p.jax_device_loop:
            from repro.core import jax_device_loop
            self.device_loop_why = jax_device_loop._device_loop_ok(self)[1]

    def _use_device_loop(self) -> bool:
        """True when ``params.jax_device_loop`` requests the whole-run
        device program *and* this cell is wave-formulated.  Off by
        default: the device loop trades the vectorized engine's event
        ordering for one fused ``lax.scan``, so it matches it at the
        ``device_loop.*`` parity bands instead of bit-for-bit."""
        return not self.device_loop_why

    def run(self) -> RunResult:
        if self._use_device_loop():
            from repro.core import jax_device_loop
            return jax_device_loop.run_wave_results(self)[0]
        return super().run()

    def run_stacked(self) -> list[RunResult]:
        if self._use_device_loop():
            from repro.core import jax_device_loop
            return jax_device_loop.run_wave_results(self)
        return super().run_stacked()


ENGINES["jax"] = JaxStreamSim
