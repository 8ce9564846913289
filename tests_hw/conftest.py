"""Real-hardware test lane.

Unlike tests/ (which pins the virtual 8-device CPU mesh), this lane runs
on the TPU the process sees. It needs the chip and FAILS without one:

    python -m pytest tests_hw -q          # one process; it owns the chip

It is OUTSIDE tests/ because pytest runs one process and the CPU pinning
in tests/conftest.py cannot be undone once jax initializes. Timing lives
in tools/kernel_bench.py; every test here states a correctness property.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "hardware: needs a real accelerator (excluded from the "
        "CPU-mesh suite)")
    from brpc_tpu.tpu.compile_cache import enable_compile_cache

    enable_compile_cache()


@pytest.fixture(scope="session")
def tpu_device():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        pytest.fail(f"tests_hw needs a TPU; JAX reports platform="
                    f"{dev.platform!r} (run without JAX_PLATFORMS=cpu, "
                    f"on the chip)", pytrace=False)
    return dev
