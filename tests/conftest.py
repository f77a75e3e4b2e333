"""Test fixtures.

Mirrors the reference's conftest strategy (python/ray/tests/conftest.py
ray_start_regular): a session-scoped runtime fixture plus per-test cluster
fixtures.  TPU/mesh tests run on a virtual 8-device CPU mesh via XLA_FLAGS
(SURVEY.md §4 testing blueprint) so multi-chip logic is tested without TPUs.
"""

import os
import sys

# Must run before jax backends initialize anywhere in the test process:
# force the virtual 8-device CPU mesh.  The recipe lives in
# __graft_entry__._force_virtual_cpu so the driver's dryrun and the test
# suite provision identical meshes.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from __graft_entry__ import _force_virtual_cpu  # noqa: E402

_force_virtual_cpu(8)

import pytest  # noqa: E402


@pytest.fixture
def ray_start_regular():
    import ray_tpu

    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def ray_start_shared():
    import ray_tpu

    rt = ray_tpu.init(num_cpus=8)
    yield rt
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 with no chip attached (tests/aot.py): libtpu is
    loaded by the xdist worker that first asks, the compile cache is off
    while the module runs."""
    import aot

    yield from aot.describe_v5e()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def step_program(request, topo):
    """`aot.compile_step`'s four (compiled, taken, train group, parameter
    count) of the whole step program of the cell whose file asks: its `CONFIG`
    names the configuration, its `STEP_STATIC`, where it has one, the rung.
    One compile a module, which every test of the step shares."""
    import aot

    return aot.compile_step(topo, request.module.CONFIG,
                            **getattr(request.module, "STEP_STATIC", {}))


# ---------------------------------------------------------------------------
# Hang watchdog: any single test exceeding WATCHDOG_S dumps EVERY
# thread's stack to the real stderr (bypassing capture) and kills the
# run — a wedged test must produce a diagnosis, not a silent stall.
# Disable with RAY_TPU_TEST_WATCHDOG=0.

import faulthandler  # noqa: E402
import os as _os  # noqa: E402

_WATCHDOG_S = float(_os.environ.get("RAY_TPU_TEST_WATCHDOG", "420"))
# A dedicated fd: pytest's fd-level capture dup2's over fd 2, so a dump
# aimed at sys.__stderr__ would vanish into the capture tmpfile.
_WATCHDOG_LOG = _os.environ.get("RAY_TPU_TEST_WATCHDOG_LOG",
                                "/tmp/ray_tpu_test_watchdog.log")
_watchdog_file = open(_WATCHDOG_LOG, "a") if _WATCHDOG_S > 0 else None


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    if _watchdog_file is not None:
        _watchdog_file.write(f"::watchdog arm {item.nodeid}\n")
        _watchdog_file.flush()
        faulthandler.dump_traceback_later(
            _WATCHDOG_S, exit=True, file=_watchdog_file)
    yield
    if _watchdog_file is not None:
        faulthandler.cancel_dump_traceback_later()


@pytest.fixture
def time_limit():
    """`time_limit(seconds)`: the test fails once it has run that long (at
    the next return to Python: a compile is not cut), where the watchdog
    above would kill the whole run.  For tests that compile."""
    import signal

    def arm(seconds: int):
        def on_alarm(signum, frame):
            raise TimeoutError(f"the test ran over its {seconds} s")

        signal.signal(signal.SIGALRM, on_alarm)
        signal.alarm(seconds)

    yield arm
    signal.alarm(0)
    signal.signal(signal.SIGALRM, signal.SIG_DFL)
