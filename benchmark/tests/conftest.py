"""Tests of the benchmark itself. They run on the CPU: jax is held to it
before anything initialises a backend, with four virtual devices for the
sharded paths. Nothing here is a measurement."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4"
)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
