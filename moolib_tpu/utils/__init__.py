"""Utility layer. ``nest`` is imported lazily because it pulls in jax, and
control-plane-only processes (broker CLI, actors without a local model) must
not pay JAX initialization cost (see moolib_tpu/__init__.py)."""

import importlib

from .checkpoint import (CheckpointError, Checkpointer, load_checkpoint,
                         save_checkpoint)
from .logging import get_logger, set_log_level, set_logging
from .stats import StatMax, StatMean, StatSum, Stats
from .timer import Ewma, Timer

__all__ = [
    "nest",
    "get_logger",
    "set_log_level",
    "set_logging",
    "StatMax",
    "StatMean",
    "StatSum",
    "Stats",
    "Ewma",
    "Timer",
    "CheckpointError",
    "Checkpointer",
    "save_checkpoint",
    "load_checkpoint",
    "stage_host_async",
]


def stage_host_async(tree):
    """Start (but do not wait for) D2H transfer of every device leaf.

    ``jax.Array.copy_to_host_async`` kicks off the transfer and caches the
    result, so a later host conversion of the same array is a wait-free
    (or nearly so) fetch. The ONE shared implementation of this idiom —
    the Accumulator stages gradient bundles with it and the examples stage
    per-update metrics (the reference's analogue is async pinned-memory
    copies, reference: src/accumulator.cc:941-980). Non-device leaves pass
    through untouched; returns the tree unchanged for chaining."""
    from . import nest

    def stage(x):
        start = getattr(x, "copy_to_host_async", None)
        if start is not None:
            start()
        return x

    return nest.map_structure(stage, tree)


def __getattr__(name: str):
    if name == "nest":
        return importlib.import_module("moolib_tpu.utils.nest")
    raise AttributeError(f"module 'moolib_tpu.utils' has no attribute {name!r}")
