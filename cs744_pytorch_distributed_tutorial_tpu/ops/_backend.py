"""The kernel layer's default for "compile or interpret?". Pallas kernels
Mosaic-compile only on TPU and run in interpret mode everywhere else.
"""

from __future__ import annotations

import jax


def default_interpret() -> bool:
    """Interpret kernels when the GLOBAL default backend is not a TPU.
    For computations targeting a non-default device set (a CPU test mesh
    on a TPU host), decide from the mesh instead —
    ``parallel/mesh.py::interpret_kernels``."""
    return jax.default_backend() != "tpu"
