"""Where a Pallas kernel runs: compiled on a TPU, interpreted on the CPU.

Every kernel entry point resolves its mode here, from the platform JAX
runs on, so no caller passes a flag and no TPU run can fall back to the
interpreter by accident.
"""
from __future__ import annotations

from jax import default_backend


def pallas_interpret() -> bool:
    """The ``interpret`` argument for `pl.pallas_call`.

    The compiled kernel on a TPU and the interpreter on the CPU, where the
    tests run; any other platform raises.  The compile tests steer
    `default_backend` to ``"tpu"`` to lower for a described chip."""
    platform = default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on a TPU or interpreted on the CPU; "
        f"the default backend is {platform!r}")
