"""Jitted learner steps: IMPALA/V-trace and A2C losses over a device mesh.

Capability parity with the reference's learner loops
(reference: examples/vtrace/experiment.py:364-529 compute_gradients/step and
examples/a2c.py:150-220), redesigned TPU-first:

- the entire update (forward, V-trace targets, loss, backward, gradient
  mean over the ``dp`` mesh axis, optimizer step) is ONE jitted XLA
  computation — the reference splits forward/backward (torch autograd) from
  the gradient allreduce (Accumulator RPC machinery,
  src/accumulator.cc:880-1033); here the allreduce is an XLA collective on
  ICI inside the step, so it overlaps with backward automatically;
- batches are time-major [T, B, ...] and sharded over ``dp`` along the batch
  axis with ``shard_map``; parameters/optimizer state are replicated;
- donation of (params, opt_state) avoids a full parameter copy in HBM per
  step.

The elastic cross-host path (virtual batch sizes, joiners/leavers) stays in
:mod:`moolib_tpu.parallel.accumulator`; this module is the dense data plane
below it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .ops import vtrace
from .parallel.mesh import batch_specs, dp_average_grads

__all__ = [
    "ImpalaConfig",
    "TrainState",
    "make_train_state",
    "impala_loss",
    "make_impala_train_step",
    "make_grad_step",
    "make_apply_step",
    "make_act_step",
]


@dataclasses.dataclass(frozen=True)
class ImpalaConfig:
    """Loss hyperparameters (reference: examples/vtrace/config.yaml:47-58)."""

    discounting: float = 0.99
    baseline_cost: float = 0.5
    entropy_cost: float = 0.0006
    reward_clip: float = 1.0  # 0 disables clipping
    lambda_: float = 1.0
    clip_rho_threshold: float = 1.0
    clip_pg_rho_threshold: float = 1.0
    # Weight of a model's own multi-token-prediction cross-entropy, where
    # its aux carries one (DecoderLM with ``mtp``, or with further
    # prediction heads, ``num_pred_heads``).
    mtp_cost: float = 0.1


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jax.Array  # scalar int32


def make_train_state(params, optimizer: optax.GradientTransformation) -> TrainState:
    return TrainState(
        params=params,
        opt_state=optimizer.init(params),
        step=jnp.zeros((), jnp.int32),
    )


def _entropy(logits):
    """Mean policy entropy (positive), [.., A] logits."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    p = jnp.exp(logp)
    return -jnp.mean(jnp.sum(p * logp, axis=-1))


def _check_axes(batch, logits_shape, baseline_shape, grouped: bool):
    """The learn-batch contract's lengths, held where the loss is traced:
    leaves that disagree would otherwise broadcast, or slice to nothing."""
    steps = batch["done"].shape[0] - 1
    actions = batch["actions"].shape[0]
    lengths = (
        f"done {batch['done'].shape}, rewards {batch['rewards'].shape}, "
        f"actions {batch['actions'].shape}, behavior_logits "
        f"{batch['behavior_logits'].shape}; the model gave logits "
        f"{logits_shape} and a baseline {baseline_shape}"
    )
    wrong = (
        batch["rewards"].shape != batch["done"].shape
        or batch["behavior_logits"].shape[:2] != batch["actions"].shape
    )
    if grouped:
        lengths += f"; action_step {batch['action_step'].shape}"
        wrong = (
            wrong
            or batch["action_step"].shape != batch["actions"].shape
            or logits_shape[0] != actions
            or baseline_shape[0] <= actions
        )
    else:
        wrong = (
            wrong or actions != steps
            or logits_shape[0] != steps + 1 or baseline_shape[0] != steps + 1
        )
    if wrong:
        raise ValueError(
            "the learn batch's leaves disagree on their axes (a batch "
            + ("with action_step: N token-actions, T steps, logits [N], a "
               "baseline [N + K], done and rewards [T + 1]" if grouped
               else "of T steps: T + 1 frames, T actions")
            + "): " + lengths
        )


def impala_loss(
    params,
    apply_fn: Callable,
    batch: dict,
    config: ImpalaConfig,
) -> Tuple[jax.Array, dict]:
    """IMPALA loss on one time-major rollout batch.

    ``batch`` layout (the learn-batch contract, mirroring the reference's
    two-stage batcher output, examples/common/__init__.py:154-207):

    - ``obs``:   [T+1, B, ...]   observations (uint8 pixels or float vectors)
    - ``done``:  [T+1, B] bool   episode terminations
    - ``rewards``: [T+1, B] f32  rewards (index t = reward entering step t)
    - ``actions``: [T, B] int32  actions taken by the behavior policy
    - ``behavior_logits``: [T, B, A] f32  behavior policy logits (how a
      ``[T, 1, A]`` leaf lies in memory and which pass reads it:
      ``ops/vtrace.py``'s module docstring and ``action_logprob_path``)
    - ``core_state``: tuple of [B, ...]  RNN state at t=0 (empty for FF)

    The model is unrolled over all T+1 frames; frame T provides the
    bootstrap value.

    **An action that is a set of tokens** (optional; a denoising step of a
    block-diffusion language model). With the leaf ``action_step`` the
    batch has two time axes, ``N`` token-actions and ``T`` steps:

    - ``action_step``: [N, B] int32  the step, in ``[0, T)``, that
      token-action ``i`` belongs to; a step is the set of its tokens
    - ``actions``: [N, B] and ``behavior_logits``: [N, B, A] lie on the
      token axis, ``done`` and ``rewards`` [T+1, B] on the step axis
    - ``obs`` is whatever the model reads (for
      :class:`moolib_tpu.models.lm.DecoderLM` with ``diffusion`` a dict,
      ``{"tokens", "reveal_step"}``, on a token axis of its own)

    and the model returns logits ``[N, B, A]``, one row a token-action,
    and a baseline ``[N + K, B]``: a value a token-action and ``K >= 1``
    rows of the bootstrap frame, whose mean is the bootstrap value. A
    step's log-ratio and log-probability are its tokens' sums, its entropy
    their entropies' sum, its value their values' mean, and V-trace runs
    over the ``T`` steps (:func:`moolib_tpu.ops.vtrace.from_grouped_logits`).
    A batch without the leaf never reaches that code.

    Leaves whose axes disagree (``actions`` against the logits,
    ``rewards`` against ``done``, the steps against the model's frames)
    raise a ``ValueError`` that names the lengths, where they are traced.

    ``apply_fn`` may return an optional THIRD element, a dict of model aux.
    The one loss term it can carry is ``mtp_loss`` (a multi-token-prediction
    module's or the further prediction heads' cross-entropy), folded into
    the total with ``config.mtp_cost``; every other entry (the expert
    layer's ``moe_*`` counters, from
    :func:`moolib_tpu.models.lm.learn_apply`) passes through to the
    metrics as a counter.
    """
    out = apply_fn(
        params, batch["obs"], batch["done"], batch["core_state"]
    )
    model_aux = None
    if len(out) == 3:
        (logits, baseline), _, model_aux = out
    else:
        (logits, baseline), _ = out
    grouped = "action_step" in batch
    _check_axes(batch, logits.shape, baseline.shape, grouped)
    if grouped:
        tokens = batch["actions"].shape[0]
        bootstrap_value = jnp.mean(baseline[tokens:], axis=0)
        token_values = baseline[:tokens]
    else:
        logits, bootstrap_value = logits[:-1], baseline[-1]
        baseline = baseline[:-1]

    with jax.named_scope("moolib.loss"):
        rewards = batch["rewards"][1:]
        if config.reward_clip > 0:
            rewards = jnp.clip(
                rewards, -config.reward_clip, config.reward_clip
            )
        discounts = (
            ~batch["done"][1:]
        ).astype(jnp.float32) * config.discounting

        clips = dict(
            clip_rho_threshold=config.clip_rho_threshold,
            clip_pg_rho_threshold=config.clip_pg_rho_threshold,
            lambda_=config.lambda_,
        )
        if grouped:
            vt = vtrace.from_grouped_logits(
                behavior_policy_logits=batch["behavior_logits"],
                target_policy_logits=logits,
                actions=batch["actions"],
                action_step=batch["action_step"],
                token_values=token_values,
                discounts=discounts,
                rewards=rewards,
                bootstrap_value=bootstrap_value,
                **clips,
            )
            baseline = vt.values  # a step's: its tokens' mean
        else:
            vt = vtrace.from_logits(
                behavior_policy_logits=batch["behavior_logits"],
                target_policy_logits=logits,
                actions=batch["actions"],
                discounts=discounts,
                rewards=rewards,
                values=baseline,
                bootstrap_value=bootstrap_value,
                **clips,
            )

        pg_loss = -jnp.mean(vt.target_action_log_probs * vt.pg_advantages)
        baseline_loss = 0.5 * jnp.mean((vt.vs - baseline) ** 2)
        entropy = jnp.mean(vt.entropies) if grouped else _entropy(logits)

        total = (
            pg_loss
            + config.baseline_cost * baseline_loss
            - config.entropy_cost * entropy
        )
    metrics = {
        "total_loss": total,
        "pg_loss": pg_loss,
        "baseline_loss": baseline_loss,
        "entropy": entropy,
        "mean_baseline": jnp.mean(baseline),
    }
    if model_aux is not None:
        # ``mtp_loss`` is the one loss term; every other entry is a counter
        # of the model's own and goes to the metrics as it is.
        aux = dict(model_aux)
        if "mtp_loss" in aux:
            metrics["mtp_loss"] = aux.pop("mtp_loss")
            total = total + config.mtp_cost * metrics["mtp_loss"]
        metrics["total_loss"] = total
        metrics.update(aux)
    return total, metrics


def make_impala_train_step(
    apply_fn: Callable,
    optimizer: optax.GradientTransformation,
    config: ImpalaConfig = ImpalaConfig(),
    mesh: Optional[Mesh] = None,
    axis_name: str = "dp",
    donate: bool = True,
    loss_fn: Callable = impala_loss,
    batch_axes: Optional[dict] = None,
) -> Callable[[TrainState, dict], Tuple[TrainState, dict]]:
    """Build the jitted train step ``(state, batch) -> (state, metrics)``.

    With a ``mesh``, the step runs under ``shard_map``: the batch is split
    over ``dp`` along its batch axis, parameters are replicated, and
    gradients come back as the global mean via an ICI psum (see
    ``dp_average_grads``). Without a mesh it is a plain single-device jit.

    ``batch_axes`` maps top-level batch keys to the axis that carries the
    batch dimension; default is axis 1 (time-major [T, B, ...]) for
    everything except ``core_state``, whose leaves are [B, ...] (axis 0).
    """

    def local_loss(params, batch):
        return loss_fn(params, apply_fn, batch, config)

    def sgd(state: TrainState, grads, metrics):
        with jax.named_scope("moolib.optimizer"):
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params
            )
            params = optax.apply_updates(state.params, updates)
            metrics = dict(metrics)
            metrics["grad_norm"] = optax.global_norm(grads)
        return TrainState(params, opt_state, state.step + 1), metrics

    if mesh is None:

        def step(state: TrainState, batch):
            (_, metrics), grads = jax.value_and_grad(
                local_loss, has_aux=True
            )(state.params, batch)
            return sgd(state, grads, metrics)

        return jax.jit(step, donate_argnums=(0,) if donate else ())

    replicated = P()

    def sharded_step(state: TrainState, batch):
        def inner(state, batch):
            (_, metrics), grads = jax.value_and_grad(
                local_loss, has_aux=True
            )(state.params, batch)
            # jax.grad w.r.t. replicated params inside shard_map returns the
            # cross-device SUM of per-device mean-loss gradients; divide by
            # the axis size to get the global-mean gradient.
            grads = dp_average_grads(grads, axis_name)
            metrics = jax.tree_util.tree_map(
                lambda m: jax.lax.pmean(m, axis_name), metrics
            )
            return sgd(state, grads, metrics)

        return jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(replicated, batch_specs(batch, batch_axes, axis_name)),
            out_specs=(replicated, replicated),
        )(state, batch)

    return jax.jit(sharded_step, donate_argnums=(0,) if donate else ())


def make_grad_step(
    apply_fn: Callable,
    config: ImpalaConfig = ImpalaConfig(),
    mesh: Optional[Mesh] = None,
    axis_name: str = "dp",
    loss_fn: Callable = impala_loss,
    batch_axes: Optional[dict] = None,
    grad_scale: Optional[float] = None,
) -> Callable[[Any, dict], Tuple[Any, dict]]:
    """Build the jitted gradient step ``(params, batch) -> (grads, metrics)``.

    This is the compute half of the elastic path: the Accumulator mediates
    between gradient computation and the optimizer step (reference:
    compute_gradients → accumulator.reduce_gradients → opt.step,
    examples/vtrace/experiment.py:470-529), so grads must surface to the
    host. With a ``mesh`` the local dp-mean rides ICI inside the step; the
    Accumulator then handles the cross-cohort (DCN) reduction.

    ``grad_scale`` multiplies the gradients INSIDE the jitted step
    (typically by the local batch size, turning batch-mean grads into the
    batch-sum contribution the Accumulator's count/reduce protocol wants).
    Folding the scale in here means the host never touches gradient values
    on the update path — the reference keeps this off the training thread
    with async pinned-memory copies (reference: src/accumulator.cc:941-980);
    our equivalent is on-device scaling + ``copy_to_host_async`` staging in
    ``Accumulator.reduce_gradients``.
    """

    def local_loss(params, batch):
        return loss_fn(params, apply_fn, batch, config)

    def finish(grads, metrics):
        metrics = dict(metrics)
        metrics["grad_norm"] = optax.global_norm(grads)
        if grad_scale is not None:
            grads = jax.tree_util.tree_map(
                lambda g: g * grad_scale, grads
            )
        return grads, metrics

    if mesh is None:

        def step(params, batch):
            (_, metrics), grads = jax.value_and_grad(
                local_loss, has_aux=True
            )(params, batch)
            return finish(grads, metrics)

        return jax.jit(step)

    replicated = P()

    def sharded_step(params, batch):
        def inner(params, batch):
            (_, metrics), grads = jax.value_and_grad(
                local_loss, has_aux=True
            )(params, batch)
            grads = dp_average_grads(grads, axis_name)
            metrics = jax.tree_util.tree_map(
                lambda m: jax.lax.pmean(m, axis_name), metrics
            )
            return finish(grads, metrics)

        return jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(replicated, batch_specs(batch, batch_axes, axis_name)),
            out_specs=(replicated, replicated),
        )(params, batch)

    return jax.jit(sharded_step)


def make_apply_step(
    optimizer: optax.GradientTransformation, donate: bool = True,
) -> Callable[[TrainState, Any], TrainState]:
    """Build the jitted optimizer-apply step ``(state, grads) -> state`` for
    externally-reduced gradients (the other half of :func:`make_grad_step`)."""

    def apply(state: TrainState, grads):
        updates, opt_state = optimizer.update(
            grads, state.opt_state, state.params
        )
        params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1)

    return jax.jit(apply, donate_argnums=(0,) if donate else ())


def make_act_step(apply_fn: Callable, temperature: float = 1.0):
    """Jitted acting step for the actor loop / EnvPool double-buffering.

    ``(params, rng, obs_B, done_B, core_state) ->
    (actions_B, logits_B, new_core_state)``.

    Adds the time axis internally (T=1), samples from the softmax policy.
    The reference does this with a torch no_grad forward on the acting model
    (examples/vtrace/experiment.py:476-504); here it is one fused XLA
    computation kept resident on the TPU.
    """

    @jax.jit
    def act(params, rng, obs, done, core_state):
        # obs may be a bare array or a dict of arrays (NLE-style); add the
        # T=1 axis per leaf either way.
        obs_t = jax.tree_util.tree_map(lambda x: x[None], obs)
        (logits, _), core_state = apply_fn(
            params, obs_t, done[None], core_state
        )
        # Return the temperature-scaled logits: they must describe the
        # distribution the action was actually sampled from, since callers
        # record them as behavior_logits for V-trace importance weights.
        logits = logits[0] / temperature
        a = jax.random.categorical(rng, logits, axis=-1)
        return a, logits, core_state

    return act


def replicate_state(state: TrainState, mesh: Mesh) -> TrainState:
    """Place a TrainState fully-replicated on the mesh (host → HBM once)."""
    sharding = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), state
    )
