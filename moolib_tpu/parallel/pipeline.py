"""Pipeline parallelism: GPipe-style microbatch pipelining over a ``pp``
mesh axis, with microbatches SHARDED over the pipeline.

The reference has no pipeline parallelism (its only strategy is elastic DP,
SURVEY.md §2.5) — this is TPU-first scope completing the mesh-axis
portfolio (dp/tp/sp/pp/ep). The construction is the classic JAX SPMD
pipeline: every device holds ONE stage's parameters; microbatches enter at
stage 0, activations hop stage-to-stage with ``lax.ppermute`` inside a
``lax.scan`` over ``n_micro + n_stages - 1`` ticks (the fill/drain bubble),
and the last stage collects outputs. All devices execute the same program —
stage identity is data (``axis_index``), exactly how XLA wants SPMD control
flow.

Memory design (the part that matters at scale): inputs and outputs are
sharded ``1/pp`` per device in a round-robin layout and ROTATE around the
pipeline ring one hop per tick, so stage 0 always holds the next microbatch
to feed and the last stage always holds the buffer slot the emerging output
belongs to. Per-device activation memory is O(n_micro/pp + 1), not
O(n_micro): no device ever materializes the full microbatch stream, and no
full-size psum broadcast happens at the end (a single cyclic ppermute
aligns the output shards).

Why round-robin works: with microbatch ``m`` initially resident on device
``m % pp`` at local slot ``m // pp`` and the input buffer rotating
``d -> d-1`` every tick, device 0 at tick ``t`` holds exactly microbatch
``t`` at slot ``t // pp``. Outputs written on the last stage at slot
``pos // pp`` plus the same rotation land (after one reverse ppermute) on
device ``pos % pp`` at slot ``pos // pp`` — the same layout as the inputs.
Both need ``pp | n_micro`` (enforced by :func:`shard_microbatches`).

Differentiability is free: scan + ppermute transpose cleanly, so the
backward pass is the reverse pipeline (activations flow backward along the
ring) without a custom VJP.

Constraints (standard for ppermute pipelines): every stage maps activations
of one shape to the SAME shape ([microbatch, features] -> same), and stage
parameters must be a pytree stacked on a leading stage axis sharded over
``pp`` (see :func:`stack_stage_params`).

Usage::

    mesh = make_mesh(pp=4, ...)
    stacked = stack_stage_params(stages)           # shard P('pp', ...)
    x_sh = shard_microbatches(x, pp)               # [k, pp, mb, F]
    y_sh = jax.jit(jax.shard_map(
        lambda p, x: pipeline_apply(stage_fn, p, x, axis_name="pp"),
        mesh=mesh, in_specs=(P("pp"), MICRO_SPEC), out_specs=MICRO_SPEC,
    ))(stacked, x_sh)
    y = unshard_microbatches(y_sh)                 # [n_micro, mb, F]
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .mesh import pvary_if_needed

__all__ = [
    "pipeline_apply",
    "pipeline_train_1f1b",
    "stack_stage_params",
    "shard_microbatches",
    "unshard_microbatches",
    "MICRO_SPEC",
]

# PartitionSpec for arrays produced by shard_microbatches: [k, pp, mb, ...]
# with the pipeline axis second.
MICRO_SPEC = P(None, "pp")


def stack_stage_params(param_list) -> Any:
    """Stack per-stage parameter pytrees on a new leading axis: shard the
    result over ``pp`` (e.g. ``P('pp', ...)``) so each device holds its
    stage's slice."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *param_list
    )


def shard_microbatches(microbatches: jax.Array, n_stages: int) -> jax.Array:
    """[n_micro, mb, ...] -> [n_micro//pp, pp, mb, ...] round-robin layout
    for ``in_specs=MICRO_SPEC``: device d's local slot s holds microbatch
    ``s * pp + d``."""
    n_micro = microbatches.shape[0]
    if n_micro % n_stages:
        raise ValueError(
            f"n_micro ({n_micro}) must be divisible by the pipeline size "
            f"({n_stages}) to shard the microbatch stream"
        )
    return microbatches.reshape(
        (n_micro // n_stages, n_stages) + microbatches.shape[1:]
    )


def unshard_microbatches(sharded: jax.Array) -> jax.Array:
    """Inverse of :func:`shard_microbatches`."""
    return sharded.reshape((-1,) + sharded.shape[2:])


def pipeline_apply(
    stage_fn: Callable,
    stage_params: Any,
    microbatches: jax.Array,
    axis_name: str = "pp",
    remat: bool = False,
):
    """Run the local microbatch shard through the stage pipeline. Call
    INSIDE shard_map (uses ``axis_index``).

    Args:
      stage_fn: ``(params, x_mb) -> y_mb`` for ONE stage; activation shape
        preserved.
      stage_params: this device's stage slice — leaves with leading dim 1
        (from a ``P('pp', ...)``-sharded stack built by
        :func:`stack_stage_params`).
      microbatches: ``[k, 1, mb, ...]`` — this device's shard of the
        round-robin layout built by :func:`shard_microbatches` with
        ``in_specs=MICRO_SPEC`` (local slot s = microbatch ``s*pp + d``).
      remat: rematerialize each stage application in the backward pass
        (``jax.checkpoint``) instead of stashing its internals — under
        ``jax.grad`` the scan otherwise saves every tick's stage
        intermediates, which dominates activation memory for deep stages.
        With remat the per-tick stash shrinks to the carry, trading one
        extra stage forward per tick in the backward (the classic
        activation/FLOPs trade 1F1B also makes).

    Returns ``[k, 1, mb, ...]`` output shards in the same layout
    (``out_specs=MICRO_SPEC``; :func:`unshard_microbatches` restores
    ``[n_micro, ...]``).
    """
    n_stages = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    # Local shard arrives [k, 1, mb, ...] (the pp axis is sharded away).
    squeeze = microbatches.shape[1] == 1
    inp0 = microbatches[:, 0] if squeeze else microbatches
    k = inp0.shape[0]
    n_micro = k * n_stages
    params = jax.tree_util.tree_map(lambda p: p[0], stage_params)
    if remat:
        stage_fn = jax.checkpoint(stage_fn)
    # Activation chain: stage d sends to d+1; stage 0 receives nothing
    # (ppermute delivers zeros to unlisted destinations, which stage 0
    # ignores — it reads from the input shard).
    chain = [(d, d + 1) for d in range(n_stages - 1)]
    # Buffer rotation ring: d -> d-1 brings future input blocks toward
    # stage 0 (and cycles output buffers past the last stage).
    ring = [(d, (d - 1) % n_stages) for d in range(n_stages)]

    def pv(x):
        return pvary_if_needed(x, axis_name)

    act0 = pv(jnp.zeros_like(inp0[0]))
    out0 = pv(jnp.zeros_like(inp0))
    inp0 = pv(inp0)

    def tick(carry, t):
        inp, act_in, out = carry
        # After t rotations device 0 holds the shard born on device t%pp;
        # slot t//pp of it is microbatch t (clamped: drain ticks read a
        # stale slot whose result never reaches the output window).
        slot = jnp.clip(t // n_stages, 0, k - 1)
        mb_t = jax.lax.dynamic_index_in_dim(inp, slot, 0, keepdims=False)
        x = jnp.where(idx == 0, mb_t, act_in)
        y = stage_fn(params, x)
        # Last stage stores microbatch pos = t-(pp-1) once it emerges, at
        # its round-robin slot; rotation carries it to its home device.
        pos = t - (n_stages - 1)
        store = jnp.logical_and(idx == n_stages - 1, pos >= 0)
        out_slot = jnp.clip(pos // n_stages, 0, k - 1)
        stored = jax.lax.dynamic_update_index_in_dim(
            out, y.astype(out.dtype), out_slot, 0
        )
        out = jnp.where(store, stored, out)
        act_next = jax.lax.ppermute(y, axis_name, chain)
        inp = jax.lax.ppermute(inp, axis_name, ring)
        out = jax.lax.ppermute(out, axis_name, ring)
        return (inp, act_next, out), None

    (_, _, out), _ = jax.lax.scan(
        tick, (inp0, act0, out0), jnp.arange(n_micro + n_stages - 1)
    )
    # One reverse hop aligns every output shard with its home device
    # (device m%pp, slot m//pp — the input layout).
    out = jax.lax.ppermute(
        out, axis_name, [(d, (d + 1) % n_stages) for d in range(n_stages)]
    )
    return out[:, None] if squeeze else out

def pipeline_train_1f1b(
    stage_fn: Callable,
    loss_fn: Callable,
    stage_params: Any,
    microbatches: jax.Array,
    axis_name: str = "pp",
):
    """Scheduled 1F1B training pipeline: warmup / steady one-forward-
    one-backward / drain, with explicit per-stage backward and weight-grad
    accumulation. Call INSIDE shard_map.

    Where :func:`pipeline_apply` + ``jax.grad`` differentiates through the
    whole pipeline scan (GPipe: all forwards, then all backwards — the scan
    stashes every tick's carry for the backward, O(ticks * carry) memory
    even under remat), 1F1B interleaves each microbatch's backward as soon
    as its forward has drained past the last stage. The backward here is
    EXPLICIT — per-tick ``jax.vjp`` of one stage application against a
    stashed input — so autodiff never sees the scan and the stash is a
    fixed ``pp``-slot ring per device: the 1F1B in-flight invariant (stage
    ``d`` holds at most ``pp - d`` live activations) bounds it.

    Schedule (sub-tick units; one tick = one F or one B per device; S =
    pp stages, M microbatches, device d, microbatch m) — the lockstep
    just-in-time variant of PipeDream-flush:

    - forward:   t = d + 2m           (even (t - d) phase)
    - backward:  t = 2S - 1 - d + 2m  (odd (t - d) phase)

    Dependencies hold by construction: F(d,m) is exactly one tick after
    F(d-1,m) and B(d,m) exactly one tick after B(d+1,m), so a single
    carry slot per direction is the whole communication buffer; the stash
    slot ``m % S`` is freed (by B of ``m``) before F of ``m+S`` reuses it
    (gap 2d+1 ticks); the per-device in-flight activation count never
    exceeds S - d — the 1F1B invariant (eager-warmup 1F1B has the same
    bound; just-in-time issue keeps the one-slot handoff of an SPMD
    lockstep ring). Total ticks T = 2M + 2(S-1): the bubble is 2(S-1)
    ticks, a fraction (S-1)/(M+S-1) — identical to GPipe's fill+drain,
    because 1F1B's win is activation MEMORY, not bubble (interleaved/
    looping schedules that also shrink the bubble are a further step, not
    taken here).

    Args:
      stage_fn: ``(params, x_mb) -> y_mb``, activation shape preserved.
      loss_fn: ``(y_mb) -> scalar`` applied to the LAST stage's output of
        each microbatch; per-microbatch losses are summed.
      stage_params: this device's stage slice (leading dim 1, from a
        ``P('pp', ...)``-sharded :func:`stack_stage_params` stack).
      microbatches: ``[M, mb, ...]`` REPLICATED across the pp axis (v1
        trades the GPipe rotation trick's input sharding for schedule
        clarity; inputs are one microbatch stream, small next to the
        O(ticks)-carry stash this schedule eliminates).

    Returns ``(loss_sum, stage_grads)`` — loss_sum replicated (psum), and
    the weight-grad accumulation for THIS device's stage with leading dim
    1 (``out_specs=P('pp', ...)`` re-stacks the pipeline).
    """
    n_stages = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    S = n_stages
    M = microbatches.shape[0]
    params = jax.tree_util.tree_map(lambda p: p[0], stage_params)

    def pv(x):
        return pvary_if_needed(x, axis_name)

    # Everything the tick body touches must be device-varying for
    # shard_map's vma typing: the replicated input stream enters varying
    # compute (each device indexes it with its own schedule).
    microbatches = pv(microbatches)
    act_shape = microbatches.shape[1:]
    dtype = microbatches.dtype
    zeros_act = jnp.zeros(act_shape, dtype)
    chain_fwd = [(d, d + 1) for d in range(S - 1)]
    chain_bwd = [(d, d - 1) for d in range(1, S)]

    carry0 = (
        pv(zeros_act),                       # act_in: fwd hop payload
        pv(zeros_act),                       # gy_in: bwd hop payload
        pv(jnp.zeros((S,) + act_shape, dtype)),  # stash: S-slot input ring
        pv(zeros_act),                       # pending_gy (last stage only)
        pv(jnp.zeros((), jnp.float32)),      # loss accumulator
        jax.tree_util.tree_map(
            lambda p: pv(jnp.zeros_like(p)), params
        ),                                   # weight-grad accumulation
    )

    def tick(carry, t):
        act_in, gy_in, stash, pending_gy, loss_acc, gacc = carry

        # -- schedule masks (device-local, data-dependent control flow) --
        # Just-in-time forwards: F(d, m) at t = d + 2m, B(d, m) at
        # t = 2S-1-d + 2m. Production is always exactly one tick before
        # consumption on the neighbor (both directions), so one carry slot
        # per direction suffices; F uses the even (t-d) phase, B the odd.
        tf = t - idx
        m_f = tf // 2
        do_f = jnp.logical_and(
            jnp.logical_and(tf >= 0, tf % 2 == 0), m_f < M
        )
        tb = t - (2 * S - 1 - idx)
        m_b = tb // 2
        do_b = jnp.logical_and(
            jnp.logical_and(tb >= 0, tb % 2 == 0), m_b < M
        )

        # -- forward ------------------------------------------------------
        mb_t = jax.lax.dynamic_index_in_dim(
            microbatches, jnp.clip(m_f, 0, M - 1), 0, keepdims=False
        )
        x = jnp.where(idx == 0, mb_t, act_in)
        # False branches derive their zeros from the operands (x * 0) so
        # both cond branches carry the same device-varying vma type.
        y = jax.lax.cond(
            do_f,
            lambda x: stage_fn(params, x).astype(dtype),
            lambda x: x * jnp.zeros((), dtype),
            x,
        )
        stash = jnp.where(
            do_f,
            jax.lax.dynamic_update_index_in_dim(
                stash, x.astype(dtype), jnp.clip(m_f, 0, M - 1) % S, 0
            ),
            stash,
        )
        # Last stage: per-microbatch loss value + dL/dy, kept for the very
        # next tick's backward of the same microbatch.
        is_last = idx == S - 1
        def loss_and_grad(y):
            lv, gy = jax.value_and_grad(loss_fn)(y)
            # f32 accumulator regardless of activation/loss dtype (bf16
            # torsos must not force a bf16 loss sum).
            return lv.astype(jnp.float32), gy.astype(dtype)

        lval, gy = jax.lax.cond(
            jnp.logical_and(do_f, is_last),
            loss_and_grad,
            lambda y: (
                jnp.sum(y).astype(jnp.float32) * 0.0,
                y * jnp.zeros((), dtype),
            ),
            y,
        )
        loss_acc = loss_acc + lval
        pending_gy = jnp.where(jnp.logical_and(do_f, is_last), gy,
                               pending_gy)

        # -- backward -----------------------------------------------------
        x_saved = jax.lax.dynamic_index_in_dim(
            stash, jnp.clip(m_b, 0, M - 1) % S, 0, keepdims=False
        )
        dy = jnp.where(is_last, pending_gy, gy_in)

        def bwd(opnd):
            x_saved, dy = opnd
            _, vjp = jax.vjp(stage_fn, params, x_saved)
            dparams, dx = vjp(dy.astype(dtype))
            return dparams, dx.astype(dtype)

        dp, dx = jax.lax.cond(
            do_b,
            bwd,
            lambda opnd: (
                jax.tree_util.tree_map(
                    lambda p: p * jnp.zeros((), p.dtype), params
                ),
                opnd[0] * jnp.zeros((), dtype),
            ),
            (x_saved, dy),
        )
        gacc = jax.tree_util.tree_map(jnp.add, gacc, dp)

        # -- hops ---------------------------------------------------------
        act_next = jax.lax.ppermute(y, axis_name, chain_fwd)
        gy_next = jax.lax.ppermute(dx, axis_name, chain_bwd)
        return (act_next, gy_next, stash, pending_gy, loss_acc, gacc), None

    T = 2 * M + 2 * (S - 1)
    (_, _, _, _, loss_acc, gacc), _ = jax.lax.scan(
        tick, carry0, jnp.arange(T)
    )
    loss_sum = jax.lax.psum(loss_acc, axis_name)
    grads = jax.tree_util.tree_map(lambda g: g[None], gacc)
    return loss_sum, grads
