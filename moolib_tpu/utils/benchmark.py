"""Shared honest-timing harness for train-step benchmarks.

The protocol (used by bench.py, tools/perf_sweep.py, and anything else that
quotes steps/s) lives HERE, once:

1. ``iters`` chained steps INSIDE one jit (``lax.fori_loop``) — per-dispatch
   timing overstates throughput when the runtime pipelines dispatches;
2. the timed quantity ends in a host readback of a scalar fingerprint of
   the updated parameters: a device-to-host value transfer cannot complete
   before the work that produces the value has.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Optional, Tuple

__all__ = [
    "time_train_step",
    "time_chained",
]


def time_chained(step, carry, iters: int = 10):
    """Time ``iters`` data-dependent applications of ``step(carry) ->
    carry`` chained INSIDE one jit (``lax.fori_loop``), ending in a D2H
    scalar fingerprint readback — the same honest protocol as
    :func:`time_train_step` for steps that aren't train-state shaped.

    Returns ``(final_carry, timed_seconds, compile_seconds)``.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def run_many(carry):
        c = jax.lax.fori_loop(0, iters, lambda _, c: step(c), carry)
        fingerprint = sum(
            jnp.sum(leaf.astype(jnp.float32))
            for leaf in jax.tree_util.tree_leaves(c)
        )
        return c, fingerprint

    t_c = time.perf_counter()
    carry, fp = run_many(carry)
    float(fp)
    compile_s = time.perf_counter() - t_c
    t0 = time.perf_counter()
    carry, fp = run_many(carry)
    assert np.isfinite(float(fp))
    dt = time.perf_counter() - t0
    return carry, dt, compile_s


def time_train_step(
    step: Callable, state, batch, iters: int = 10,
    trace_dir: Optional[str] = None,
) -> Tuple[Any, float, float]:
    """Time ``iters`` chained ``step(state, batch) -> (state, metrics)``
    calls under the honest protocol.

    Returns ``(final_state, timed_seconds, compile_seconds)`` — throughput
    is ``iters * items_per_step / timed_seconds``. With ``trace_dir``, an
    XLA profiler trace captures ONLY the timed run (compilation and warmup
    would otherwise dwarf the steady-state timeline).
    """
    import contextlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def run_many(state, batch):
        def body(_, s):
            s, _metrics = step(s, batch)
            return s

        s = jax.lax.fori_loop(0, iters, body, state)
        fingerprint = sum(
            jnp.sum(leaf.astype(jnp.float32))
            for leaf in jax.tree_util.tree_leaves(s.params)
        )
        return s, fingerprint

    t_c = time.perf_counter()
    state, fp = run_many(state, batch)  # compile + warmup
    float(fp)
    compile_s = time.perf_counter() - t_c

    if trace_dir:
        from .profiling import profile_trace

        ctx = profile_trace(trace_dir)
    else:
        ctx = contextlib.nullcontext()
    with ctx:
        t0 = time.perf_counter()
        state, fp = run_many(state, batch)
        assert np.isfinite(float(fp))  # D2H readback: forces real completion
        dt = time.perf_counter() - t0
    return state, dt, compile_s
