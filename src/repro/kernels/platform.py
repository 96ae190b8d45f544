"""The one place the platform picks Pallas kernel against jnp reference.

On TPU every Pallas kernel runs compiled and the serving paths call the
kernels; everywhere else the serving paths take their jnp twins, and a
kernel called directly (parity tests, benchmarks) runs in Pallas
interpret mode.
"""

from __future__ import annotations

import jax


def kernels_compiled() -> bool:
    """True where the Pallas kernels compile for the device (TPU)."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Kernel ``interpret`` flag: None means off on TPU, on elsewhere."""
    return (not kernels_compiled()) if interpret is None else bool(interpret)
