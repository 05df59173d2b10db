"""Where the Pallas kernels run: Mosaic on a TPU, the interpreter elsewhere."""

from __future__ import annotations

import jax


def default_interpret() -> bool:
    """Interpret a kernel only when the default backend is not a TPU.

    Every kernel entry point takes ``interpret=None`` to mean this, so no
    caller on the chip runs the Python interpreter by omission, and CPU tests
    still exercise the same kernel bodies.
    """
    return jax.default_backend() != "tpu"
