"""Test harness configuration.

Tests run on CPU with 8 virtual XLA devices so multi-chip sharding
(dp/tp/sp meshes, collectives) is exercised without TPU hardware.  Must run
before the first ``import jax`` anywhere in the test process.

One limit per test: a test that waits for ever fails with the traceback of
where it waited, and the rest of its file runs.
"""

import asyncio
import io
import os
import signal

import pytest

# Force CPU even when the environment pins JAX_PLATFORMS to a TPU platform:
# the suite needs 8 virtual devices for sharding tests.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()


# At least three times the slowest honest test beside five busy workers
# (about 40 s on 8 cores), and far under the whole run's own clock.
TEST_LIMIT_S = 180.0


def _over_limit(signum, frame):
    waits = io.StringIO()
    try:
        # the traceback ends in the loop's select(); the coroutines say more
        for task in asyncio.all_tasks():
            task.print_stack(file=waits)
    except RuntimeError:
        pass  # no event loop is running: the traceback says where
    pytest.fail(
        f"over the limit of {TEST_LIMIT_S:.0f} s for one test (tests/conftest.py)\n"
        + waits.getvalue()
    )


@pytest.fixture(autouse=True)
def _test_limit():
    """SIGALRM reaches the main thread, where pytest and xdist's workers run
    the tests, and interrupts an event loop's ``select``.  It comes again
    every few seconds until the test is over, because the first one only
    moves a wait that sits in a ``finally`` into ``asyncio.run``'s cleanup."""
    signal.signal(signal.SIGALRM, _over_limit)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S, 5.0)
    yield
    signal.setitimer(signal.ITIMER_REAL, 0)
