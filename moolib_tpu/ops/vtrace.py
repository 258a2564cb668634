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

Definitions (paper eq. 1):
    delta_t = rho_t (r_t + gamma_t V(x_{t+1}) - V(x_t))
    v_t     = V(x_t) + delta_t + gamma_t c_t (v_{t+1} - V(x_{t+1}))
    rho_t   = min(rho_bar, pi(a_t|x_t) / mu(a_t|x_t))
    c_t     = lambda * min(c_bar, pi(a_t|x_t) / mu(a_t|x_t))
with policy-gradient advantages rho_t (r_t + gamma_t v_{t+1} - V(x_t)),
where the rho used for advantages is clipped at ``clip_pg_rho_threshold``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["VTraceReturns", "VTraceFromLogitsReturns", "from_importance_weights",
           "from_logits", "action_log_probs"]


class VTraceReturns(NamedTuple):
    vs: jax.Array
    pg_advantages: jax.Array


class VTraceFromLogitsReturns(NamedTuple):
    vs: jax.Array
    pg_advantages: jax.Array
    log_rhos: jax.Array
    behavior_action_log_probs: jax.Array
    target_action_log_probs: jax.Array


def action_log_probs(policy_logits: jax.Array, actions: jax.Array) -> jax.Array:
    """log pi(a|x) for integer actions over a final logits axis."""
    logp = jax.nn.log_softmax(policy_logits, axis=-1)
    return jnp.take_along_axis(logp, actions[..., None], axis=-1).squeeze(-1)


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
    with jax.named_scope("moolib.vtrace"):
        behavior_log_probs = action_log_probs(behavior_policy_logits, actions)
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
