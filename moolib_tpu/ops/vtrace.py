"""V-trace off-policy actor-critic targets, TPU-native.

Capability parity with the reference's torch V-trace port
(reference: examples/common/vtrace.py, itself derived from the IMPALA paper,
Espeholt et al. 2018, arXiv:1802.01561). Written directly from the paper's
equations: no Python-side loops, static shapes, time-major [T, B] layout.

The backwards recursion ``acc_t = delta_t + gamma_t c_t acc_{t+1}`` is
first-order and linear, and affine maps ``x -> a x + b`` compose
associatively, so it runs as ``jax.lax.associative_scan`` over the pairs
``(a_t, b_t) = (gamma_t c_t, delta_t)``: ``log2 T`` levels of whole-array
operations and no loop, at every length, chosen from nothing. Why: as one
``lax.scan`` step a time step, a ``while`` of ``T`` iterations of ``f32[1,1]``
fusions in a dependent chain with nothing beside it on the chip, the four
decoder cells of the benchmark (one packed sequence, ``T`` 8,191 / 8,191 /
4,095 / 16,383) read 23.65 / 16.97 / 9.61 / 24.41 ms a learner step in this
module's scope (``vtrace.device_ms_per_step``, ledger, PR 40), and on one v5e
chip the function alone read 24.5 ms at ``T`` = 16,383 against 0.15 in this
form; at IMPALA's ``[20, 256]`` and NetHack's ``[80, 128]`` it read 4.2 and
8.2 us against 3.4 and 3.5 (PERF.md, Findings PR 41), so the scan has no
length left at which it is the better form. A ``[T, 1]`` intermediate is laid
out by XLA with time on the lanes, so a narrow batch pads nothing.

An episode's end makes ``a_t = 0``; the composition multiplies and adds and
never divides, so it cuts the recursion exactly. Everything is computed in
the inputs' float32 and agrees with a float64 oracle to 1.3e-6 absolute on
values of 10 at ``T`` = 16,383, as the step-by-step form does
(``tests/test_vtrace.py``).

**The behaviour policy's log-probabilities** (:func:`action_logprob_path`).
A learn batch's ``behavior_logits`` float32 ``[T, 1, A]`` reach a step in
``{2,1,0:T(1,128)}``: a middle axis of 1 tiles by one sublane, so a row is
``A`` contiguous numbers. XLA emits ``log_softmax``'s row maximum over that
layout as a bare ``reduce`` on vectors an eighth full: 7.9 ms a step for 402
MB at ``[8191, 1, 12288]`` and ``[4095, 1, 24576]``, sixteen times the bytes'
time and the longest operation of two cells, and no re-spelling in
``jax.numpy`` cures it (PERF.md, Findings "PR 41" and "PR 44"). Where ``A`` is
a whole number of (8,128) tiles the same bytes are ``[T, A/128, 128]`` tiled
by (8,128), which XLA takes as a bitcast: such logits, where large, are
``"streamed"``, one Pallas pass that reads each row once as whole vectors for
maximum, sum and the action's entry (0.54 ms there, the bytes' time). Every
other shape, dtype and backend is ``"plain"``: ``[20, 256, 6]``, ``[80, 128,
23]``, ``[16383, 1, 320]``, and ``[8191, 1, 19360]``, which XLA re-lays out
for every reader already. The target logits come from the head in a layout
XLA reads well and need a gradient: they stay :func:`action_log_probs`.

**An action that is a set of tokens** (:func:`from_grouped_logits`). A
denoising step of a block-diffusion language model reveals several tokens at
once: the step is the action, its probability the product of its tokens'
probabilities under both policies, its entropy their entropies' sum, and the
recursion above runs over *steps*, fewer than tokens and of uneven size. The
logits and the chosen entries then lie on a token axis, rewards, discounts and
values on a step axis, and ``action_step`` ``[tokens, B]`` says which step a
token belongs to (:func:`group_sum`: one segment sum a column, whatever the
order of the tokens).

Definitions (paper eq. 1):
    delta_t = rho_t (r_t + gamma_t V(x_{t+1}) - V(x_t))
    v_t     = V(x_t) + delta_t + gamma_t c_t (v_{t+1} - V(x_{t+1}))
    rho_t   = min(rho_bar, pi(a_t|x_t) / mu(a_t|x_t))
    c_t     = lambda * min(c_bar, pi(a_t|x_t) / mu(a_t|x_t))
with policy-gradient advantages rho_t (r_t + gamma_t v_{t+1} - V(x_t)),
where the rho used for advantages is clipped at ``clip_pg_rho_threshold``.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry import global_telemetry

__all__ = ["VTraceReturns", "VTraceFromLogitsReturns",
           "VTraceFromGroupedLogitsReturns", "from_importance_weights",
           "from_logits", "from_grouped_logits", "group_sum",
           "action_log_probs", "action_logprob_path",
           "streamed_action_log_probs"]

LANES, SUBLANES = 128, 8


class VTraceReturns(NamedTuple):
    vs: jax.Array
    pg_advantages: jax.Array


class VTraceFromLogitsReturns(NamedTuple):
    vs: jax.Array
    pg_advantages: jax.Array
    log_rhos: jax.Array
    behavior_action_log_probs: jax.Array
    target_action_log_probs: jax.Array


class VTraceFromGroupedLogitsReturns(NamedTuple):
    vs: jax.Array  # [steps, B]
    pg_advantages: jax.Array  # [steps, B]
    log_rhos: jax.Array  # [steps, B]: a step's tokens' log-ratios, summed
    target_action_log_probs: jax.Array  # [steps, B]: log pi of the set
    entropies: jax.Array  # [steps, B]: the tokens' entropies, summed
    values: jax.Array  # [steps, B]: the tokens' values, averaged


def action_log_probs(policy_logits: jax.Array, actions: jax.Array) -> jax.Array:
    """log pi(a|x) for integer actions over a final logits axis."""
    logp = jax.nn.log_softmax(policy_logits, axis=-1)
    return jnp.take_along_axis(logp, actions[..., None], axis=-1).squeeze(-1)


# The streamed pass of the behaviour logits, on one TPU v5e (PERF.md,
# Findings "PR 44"; device time of the function alone, plain / streamed).
# [8191, 1, 12288] and [4095, 1, 24576], 403 MB: 9.78 / 0.539 and 9.73 /
# 0.536 ms (747 GB/s); [4095, 1, 16384]: 2.29 / 0.359. Blocks of 2 and 4 MB
# of logits to a grid step read the same to 1%.
STREAM_BLOCK_BYTES = 4 * 1024 * 1024
# Every action lies in scalar memory: 256 KB of its 1 MB at this many rows
# (compiled for a described v5e; four times as many are refused).
STREAM_MAX_ROWS = 65_536
# A row costs the pass three cross-lane reductions whatever its width, so the
# narrowest row it takes (1,024 actions) decides: 1 / 2 / 4 / 8 / 16 / 32 /
# 64 MB of [T, 1, 1024] read 7.0 / 15.5 / 30.0 / 58.3 / 115.6 / 230 / 580 us
# plain and 8.9 / 16.5 / 32.3 / 58.4 / 110.5 / 215 / 425 streamed. From here
# up no reading has the pass behind (at 16 MB of 4,096 and 16,384 actions
# 90 / 34 and 108 / 25 us).
STREAM_MIN_BYTES = 16 * 1024 * 1024


def action_logprob_path(shape, dtype) -> str:
    """``"streamed"`` or ``"plain"``: how ``log pi(a|x)`` of logits ``[...,
    B, A]`` that need no gradient is computed, from what a trace can see.
    The streamed pass reads the rows as they lie in row-major memory, which
    is how XLA holds them when the axis before the actions is 1 and a row
    is a whole number of (8,128) tiles (wider, ``B`` and ``A`` tile together
    and XLA reads them well itself); it knows float32; it holds a block of
    eight rows in fast memory and every action in scalar memory; it is
    worth its launch on a large array alone; and Mosaic compiles it for a
    TPU alone."""
    *lead, columns, actions = shape
    rows = math.prod(lead) * columns
    streamed = (
        jax.default_backend() == "tpu"
        and jnp.dtype(dtype) == jnp.dtype(jnp.float32)
        and columns == 1
        and actions % (SUBLANES * LANES) == 0
        and SUBLANES * actions * 4 <= STREAM_BLOCK_BYTES
        and rows <= STREAM_MAX_ROWS
        and rows * actions * 4 >= STREAM_MIN_BYTES
    )
    return "streamed" if streamed else "plain"


def _stream_kernel(actions_ref, logits_ref, out_ref):
    """``out[r] = x[a] - max(x) - log(sum(exp(x - max(x))))`` for the rows
    ``x = logits[r]`` of a block ``[rows, A/128, 128]``, a row whole vectors,
    with the row's true maximum as the plain path has it. The actions lie in
    scalar memory, all of them."""
    rows, tiles, _ = logits_ref.shape
    entry = (
        jax.lax.broadcasted_iota(jnp.int32, (tiles, LANES), 0) * LANES
        + jax.lax.broadcasted_iota(jnp.int32, (tiles, LANES), 1)
    )
    first = pl.program_id(0) * rows
    last = actions_ref.shape[0] - 1  # a ragged last block reads past it

    # over a row, the same on every lane: Mosaic refuses to broadcast a
    # (1, 1) along sublanes and lanes at once
    def whole(v, op):
        v = op(op(v, axis=0, keepdims=True), axis=1, keepdims=True)
        return jnp.broadcast_to(v, (1, LANES))

    def eight(g, carry):  # rows apart, so that their reductions overlap
        for k in range(SUBLANES):
            r = g * SUBLANES + k
            x = logits_ref[r]
            top = whole(x, jnp.max)
            total = whole(jnp.exp(x - top), jnp.sum)
            action = actions_ref[jnp.minimum(first + r, last)]
            taken = whole(jnp.where(entry == action, x, 0.0), jnp.sum)
            out_ref[pl.ds(r, 1), :] = ((taken - top) - jnp.log(total))[:, :1]
        return carry

    jax.lax.fori_loop(0, rows // SUBLANES, eight, None)


@jax.jit
def _stream(logits, actions):
    rows, width = actions.size, logits.shape[-1]
    tiles = width // LANES
    block = STREAM_BLOCK_BYTES // (width * 4) // SUBLANES * SUBLANES
    block = max(SUBLANES, min(block, -(-rows // SUBLANES) * SUBLANES))
    out = pl.pallas_call(
        _stream_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        grid=(pl.cdiv(rows, block),),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block, tiles, LANES), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block, 1), lambda i: (i, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=4 * STREAM_BLOCK_BYTES,
        ),
        # Mosaic compiles for a TPU alone. The rule sends every other
        # backend to the plain path, so only a test gets here off one:
        # Pallas' interpreter then runs the same arithmetic.
        interpret=jax.default_backend() != "tpu",
    )(actions.astype(jnp.int32).reshape(rows),
      logits.reshape(rows, tiles, LANES))
    return out.reshape(actions.shape)


@jax.custom_vjp
def streamed_action_log_probs(policy_logits, actions):
    """:func:`action_log_probs` of float32 logits whose last axis is a whole
    number of 128-lane tiles, for actions in ``[0, A)``, by one pass that
    reads every row once and writes ``[..., B]`` alone: equal to the plain
    path's to float32 rounding (the sum's order), ``-inf`` entries and all.
    The gradient with respect to the logits is the plain path's, by plain
    ``jax.numpy``."""
    return _stream(policy_logits, actions)


def _streamed_fwd(policy_logits, actions):
    return _stream(policy_logits, actions), (policy_logits, actions)


def _streamed_bwd(kept, g):
    policy_logits, actions = kept
    taken = jax.nn.one_hot(
        actions, policy_logits.shape[-1], dtype=policy_logits.dtype
    )
    return g[..., None] * (taken - jax.nn.softmax(policy_logits, axis=-1)), None


streamed_action_log_probs.defvjp(_streamed_fwd, _streamed_bwd)


def _compose(later, earlier):
    """``x -> a x + b`` of the later time steps, then of the earlier one."""
    a1, b1 = later
    a2, b2 = earlier
    return a1 * a2, b2 + a2 * b1


def from_importance_weights(
    log_rhos: jax.Array,
    discounts: jax.Array,
    rewards: jax.Array,
    values: jax.Array,
    bootstrap_value: jax.Array,
    clip_rho_threshold: float | None = 1.0,
    clip_pg_rho_threshold: float | None = 1.0,
    lambda_: float = 1.0,
) -> VTraceReturns:
    """Compute V-trace targets from log importance weights.

    Args are time-major: ``log_rhos/discounts/rewards/values`` are [T, B],
    ``bootstrap_value`` is [B]. Gradients are stopped through all inputs:
    V-trace targets are constants w.r.t. the learner parameters.
    """
    log_rhos, discounts, rewards, values, bootstrap_value = map(
        jax.lax.stop_gradient,
        (log_rhos, discounts, rewards, values, bootstrap_value),
    )
    rhos = jnp.exp(log_rhos)
    clipped_rhos = (
        jnp.minimum(clip_rho_threshold, rhos)
        if clip_rho_threshold is not None
        else rhos
    )
    cs = lambda_ * jnp.minimum(1.0, rhos)

    # values_{t+1}: shift values up by one, bootstrap at the end.
    values_t_plus_1 = jnp.concatenate(
        [values[1:], bootstrap_value[None]], axis=0
    )
    deltas = clipped_rhos * (rewards + discounts * values_t_plus_1 - values)

    # Backwards recursion: acc_t = delta_t + gamma_t c_t acc_{t+1};
    # vs_t = V(x_t) + acc_t: the affine maps composed from the last step back.
    _, accs = jax.lax.associative_scan(
        _compose, (discounts * cs, deltas), reverse=True
    )
    vs = values + accs

    vs_t_plus_1 = jnp.concatenate([vs[1:], bootstrap_value[None]], axis=0)
    pg_rhos = (
        jnp.minimum(clip_pg_rho_threshold, rhos)
        if clip_pg_rho_threshold is not None
        else rhos
    )
    pg_advantages = pg_rhos * (rewards + discounts * vs_t_plus_1 - values)
    return VTraceReturns(vs=vs, pg_advantages=pg_advantages)


def _behavior_pass(behavior_policy_logits):
    """The function that computes ``log mu(a|x)`` of these logits, which
    need no gradient: :func:`action_logprob_path`'s choice, on record."""
    # Inside a shard_map no cell's logits are large, the pass has not run
    # there on a chip and Pallas' interpreter cannot (its grid loop drops
    # the varying axes, jax 0.9.0): plain, whatever the shape.
    path = "plain" if jax.typeof(behavior_policy_logits).vma else (
        action_logprob_path(
            behavior_policy_logits.shape, behavior_policy_logits.dtype
        )
    )
    # once a trace: once a compile under jit
    global_telemetry().registry.counter(
        "vtrace_logprob_calls_traced_total", path=path
    ).inc()
    return (
        streamed_action_log_probs if path == "streamed" else action_log_probs
    )


def from_logits(
    behavior_policy_logits: jax.Array,
    target_policy_logits: jax.Array,
    actions: jax.Array,
    discounts: jax.Array,
    rewards: jax.Array,
    values: jax.Array,
    bootstrap_value: jax.Array,
    clip_rho_threshold: float | None = 1.0,
    clip_pg_rho_threshold: float | None = 1.0,
    lambda_: float = 1.0,
) -> VTraceFromLogitsReturns:
    """V-trace for softmax policies: [T, B, A] logits, [T, B] actions."""
    behavior = _behavior_pass(behavior_policy_logits)
    with jax.named_scope("moolib.vtrace"):
        behavior_log_probs = behavior(behavior_policy_logits, actions)
        target_log_probs = action_log_probs(target_policy_logits, actions)
        log_rhos = target_log_probs - behavior_log_probs
        vt = from_importance_weights(
            log_rhos=log_rhos,
            discounts=discounts,
            rewards=rewards,
            values=values,
            bootstrap_value=bootstrap_value,
            clip_rho_threshold=clip_rho_threshold,
            clip_pg_rho_threshold=clip_pg_rho_threshold,
            lambda_=lambda_,
        )
        return VTraceFromLogitsReturns(
            vs=vt.vs,
            pg_advantages=vt.pg_advantages,
            log_rhos=log_rhos,
            behavior_action_log_probs=behavior_log_probs,
            target_action_log_probs=target_log_probs,
        )


def group_sum(x: jax.Array, action_step: jax.Array, steps: int) -> jax.Array:
    """``x`` ``[tokens, B, ...]`` summed over the tokens of each step:
    ``out[u, b] = sum of x[i, b] over the i with action_step[i, b] == u``,
    ``[steps, B, ...]``. The tokens of a step may lie anywhere on the token
    axis; a step that no token names is zero; a token whose step is not in
    ``[0, steps)`` is dropped."""
    B = x.shape[1]
    flat = (action_step.astype(jnp.int32) * B + jnp.arange(B)).reshape(-1)
    flat = jnp.where(
        jnp.logical_and(action_step >= 0, action_step < steps).reshape(-1),
        flat, steps * B,
    )
    out = jax.ops.segment_sum(
        x.reshape((-1,) + x.shape[2:]), flat, num_segments=steps * B
    )
    return out.reshape((steps, B) + x.shape[2:])


def from_grouped_logits(
    behavior_policy_logits: jax.Array,
    target_policy_logits: jax.Array,
    actions: jax.Array,
    action_step: jax.Array,
    token_values: jax.Array,
    discounts: jax.Array,
    rewards: jax.Array,
    bootstrap_value: jax.Array,
    clip_rho_threshold: float | None = 1.0,
    clip_pg_rho_threshold: float | None = 1.0,
    lambda_: float = 1.0,
) -> VTraceFromGroupedLogitsReturns:
    """V-trace where an action is a *set* of categorical choices (the
    module docstring): logits ``[tokens, B, A]`` and ``actions``,
    ``action_step`` and ``token_values`` ``[tokens, B]`` on the token axis;
    ``discounts`` and ``rewards`` ``[steps, B]`` and ``bootstrap_value``
    ``[B]`` on the step axis. With ``G(u)`` the tokens of step ``u``:

        log rho_u = sum over G(u) of (log pi(a_i|x_i) - log mu(a_i|x_i))
        log pi_u  = sum over G(u) of log pi(a_i|x_i)
        H_u       = sum over G(u) of H(pi(.|x_i))
        V_u       = mean over G(u) of token_values_i

    and :func:`from_importance_weights` over the steps: the ratios are
    clipped a step, not a token. The behaviour side is
    :func:`action_logprob_path`'s pass, as in :func:`from_logits`. A step
    without a token has ``log rho`` 0, ``H`` 0 and the value 0."""
    steps = discounts.shape[0]
    behavior = _behavior_pass(behavior_policy_logits)
    with jax.named_scope("moolib.vtrace"):
        behavior_log_probs = behavior(behavior_policy_logits, actions)
        logp = jax.nn.log_softmax(target_policy_logits, axis=-1)
        target_log_probs = jnp.take_along_axis(
            logp, actions[..., None], axis=-1
        ).squeeze(-1)
        entropies = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
        # one segment sum of the five token quantities, a row a token
        log_rhos, log_pi, entropy, value_sum, count = jnp.moveaxis(group_sum(
            jnp.stack([
                target_log_probs - behavior_log_probs, target_log_probs,
                entropies, token_values, jnp.ones_like(token_values),
            ], axis=-1), action_step, steps,
        ), -1, 0)
        values = value_sum / jnp.maximum(count, 1.0)
        vt = from_importance_weights(
            log_rhos=log_rhos,
            discounts=discounts,
            rewards=rewards,
            values=values,
            bootstrap_value=bootstrap_value,
            clip_rho_threshold=clip_rho_threshold,
            clip_pg_rho_threshold=clip_pg_rho_threshold,
            lambda_=lambda_,
        )
        return VTraceFromGroupedLogitsReturns(
            vs=vt.vs,
            pg_advantages=vt.pg_advantages,
            log_rhos=log_rhos,
            target_action_log_probs=log_pi,
            entropies=entropy,
            values=values,
        )
