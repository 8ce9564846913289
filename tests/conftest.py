"""Test substrate: a virtual 8-device CPU mesh (SURVEY §4 takeaway).

The reference tests simulate a cluster with N channels to loopback servers;
we likewise simulate a TPU pod with 8 virtual CPU devices.

The CPU platform and the 8 virtual devices are pinned here, before jax is
imported and before any backend initialises: a test run never claims the
chip (tests_hw/ and chip_smoke.py are the chip lanes).
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

try:
    import jax  # noqa: E402

    jax.config.update("jax_platforms", "cpu")
except ImportError:  # non-jax environments still run the pure-RPC tests
    pass

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 run")
    config.addinivalue_line(
        "markers", "chaos: fault-injection tests (in the tier-1 budget)")


import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _brpc_tpu_check_ledger():
    """With BRPC_TPU_CHECK=1 in the environment, assert at session exit
    that every tracked credit window is whole and no borrowed block view
    is still alive. A no-op in normal runs."""
    yield
    from brpc_tpu.analysis import runtime_check as _rc

    if not _rc.ACTIVE:
        return
    try:
        from brpc_tpu.tpu.transport import _sweep_deferred_pools as _drain
    except Exception:
        _drain = None
    _rc.ledger.assert_balanced(drain=_drain)


@pytest.fixture()
def empty_registry():
    """An empty variable registry for one test. Afterwards the registry
    holds what it held before and what a module still owns: a module
    imported for the first time during the test exposed its ``g_*``
    variables then (``serving.engine`` in ``test_fleet.py``, the tunnel in
    ``test_tail_dump.py``), and they belong to every file that runs later
    in this worker. What only the test owned is gone."""
    import gc
    import weakref

    from brpc_tpu.metrics.variable import (Variable, clear_registry,
                                           exposed_variables, get_exposed)

    saved = exposed_variables()
    clear_registry()
    yield
    fresh = [(name, weakref.ref(var)) for name, var in exposed_variables()]
    clear_registry()
    gc.collect()        # a recorder and its exposed parts are a cycle
    for name, var in saved:
        Variable.expose(var, name)
    for name, ref in fresh:
        var = ref()
        if var is not None and get_exposed(name) is None:
            Variable.expose(var, name)
