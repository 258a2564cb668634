"""Test configuration: force an 8-device virtual CPU mesh.

The reference CI runs CPU-only with per-test process isolation
(reference: .github/workflows/run_python_tests.yml:33-50). We instead make the
whole suite runnable on any host by forcing the JAX CPU backend with 8 virtual
devices, so every multi-chip sharding test (dp/tp/sp meshes, psum collectives)
executes for real without TPU hardware. Environment variables must be set
before jax initializes its backends, hence module scope here.
"""

import os
import sys

# XLA_FLAGS is read when the backend initializes (lazily), so setting it here
# is safe even if some pytest plugin already imported jax — as long as no
# backend has been created yet.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

# Belt and braces: jax.config wins even if jax was imported before us.
jax.config.update("jax_platforms", "cpu")

assert len(jax.devices()) == 8, (
    "jax backend initialized before conftest.py could configure the virtual "
    f"CPU mesh (got {jax.devices()})"
)

import pytest  # noqa: E402


_faulthandler_fd = None


def pytest_configure(config):
    """Arm a whole-session faulthandler watchdog: if the suite is still
    running when the timer fires — i.e. something deadlocked and is about
    to eat the tier-1 window silently — every thread's stack is dumped so
    the hang is diagnosable from the CI log. The default sits just under
    the outer ``timeout -k 10 1470`` the driver runs tier-1 under, so the
    dump lands BEFORE SIGKILL and never in the middle of a sound run (at
    840 it fired 36 s after a sound run's end, and a dump taken while jax's
    threads run can take its worker down: PR 47's first whole run lost one
    so); ``MOOLIB_FAULTHANDLER_TIMEOUT=0`` disables, any other value
    re-tunes (tools/ci_check.sh pairs 840 with its own 870).

    The dump must go to the REAL stderr, not pytest's capture: a
    SIGKILLed session never flushes capture temp files, so a dump
    written there would be lost with the hang it describes. Dup the
    stderr fd at configure time, exactly like pytest's own per-test
    faulthandler plugin does."""
    import faulthandler

    timeout = float(os.environ.get("MOOLIB_FAULTHANDLER_TIMEOUT", "1440"))
    if timeout <= 0:
        return
    try:
        fd = sys.stderr.fileno()
        if fd == -1:
            raise ValueError
    except (AttributeError, ValueError):
        fd = sys.__stderr__.fileno()
    global _faulthandler_fd
    _faulthandler_fd = os.dup(fd)  # keep alive for the whole session
    faulthandler.dump_traceback_later(
        timeout, exit=False, file=_faulthandler_fd
    )


def pytest_unconfigure(config):
    import faulthandler

    faulthandler.cancel_dump_traceback_later()
    global _faulthandler_fd
    if _faulthandler_fd is not None:
        os.close(_faulthandler_fd)
        _faulthandler_fd = None


@pytest.fixture
def rng():
    import numpy as np

    return np.random.default_rng(0)
