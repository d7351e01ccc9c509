"""Tests that need the chip: Mosaic-compiled kernels against their
``jax.numpy`` references at production shapes, and the checks only a
real device can make (memory stats, the timing fence, collectives over
ICI). Run on the chip machine, one process:

    python -m pytest tests_chip

Off-chip this directory FAILS; a skip would read as a pass. The CPU
harness is ``tests/`` (which pins ``jax_platforms=cpu`` and is what
tier-1 runs); nothing here is collected by it.
"""

import jax
import pytest

from cs744_pytorch_distributed_tutorial_tpu.utils.compile_cache import (
    configure_compile_cache,
)


def pytest_sessionstart(session):
    device = jax.devices()[0]
    if device.platform != "tpu":
        pytest.exit(
            f"tests_chip needs a TPU, found platform {device.platform!r} "
            f"({device.device_kind!r}); run it through the chip tool",
            returncode=1,
        )
    configure_compile_cache()
