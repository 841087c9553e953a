"""Which Pallas mode the kernels run in on the default backend."""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Pallas ``interpret`` flag: interpreted on CPU, compiled on TPU.

    The CPU branch is how the test suite validates the kernels; on TPU
    they compile through Mosaic.  Any other backend raises rather than
    quietly falling back to the interpreter.
    """
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run compiled on TPU or interpreted on CPU; the "
        f"default backend is {backend!r}"
    )
