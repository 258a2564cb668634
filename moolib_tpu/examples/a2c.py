"""Single-file A2C on CartPole with an in-process Broker + elastic Accumulator.

Capability parity with the reference's A2C example (reference:
examples/a2c.py — CartPole via gym, in-process Broker + Accumulator, rollout
buffer, optional LSTM, per-rollout n-step-return policy-gradient updates),
redesigned TPU-first:

- acting and learning are jitted XLA computations (``make_act_step`` /
  ``make_grad_step``); the rollout loop only moves numpy in and out of
  :class:`moolib_tpu.EnvPool`'s shared-memory views;
- the gradient update is split compute→reduce→apply around the elastic
  :class:`moolib_tpu.Accumulator`, so extra peers can join the same broker
  address at any time and the virtual batch fills from all of them
  (run two copies of this script with ``--broker tcp://HOST:PORT`` to see it).

Run: ``python -m moolib_tpu.examples.a2c [--total-steps N] [--use-lstm]``
"""

from __future__ import annotations

import argparse
import dataclasses
import threading
import time
from typing import List, Optional

import numpy as np

import moolib_tpu
from moolib_tpu.telemetry import StepScope, publish_metrics
from moolib_tpu.examples.common import (
    EnvBatchState,
    InProcessBroker,
    StatMean,
    StatSum,
    Stats,
)

__all__ = ["A2CConfig", "train", "a2c_loss"]


@dataclasses.dataclass
class A2CConfig:
    """Defaults mirror the reference's constants (reference:
    examples/a2c.py:17-27 — rollout 64, lr 1e-3, baseline cost 0.005,
    entropy cost 0.0006, adam eps 3e-7)."""

    total_steps: int = 50_000
    # "cartpole" | "synthetic" (Atari-shaped pixels) | an ALE id like
    # "ALE/Pong-v5" (driver benchmark config 2: A2C on Atari Pong, one
    # chip, no cross-peer Accumulator needed — though it still works).
    env: str = "cartpole"
    num_actions: int = 6  # pixel envs only (cartpole is 2)
    unroll_length: int = 64
    batch_size: int = 4  # envs per peer
    num_processes: int = 2
    num_batches: int = 2  # double buffering
    use_lstm: bool = False
    hidden_size: int = 64
    learning_rate: float = 1e-3
    adam_eps: float = 3e-7
    discounting: float = 0.99
    entropy_cost: float = 0.0006
    baseline_cost: float = 0.005
    grad_clip: float = 40.0
    virtual_batch_size: Optional[int] = None  # default: one peer's batch
    # Survivable-training knobs (ISSUE 11): commit gradient rounds with
    # K-of-N contributions after the straggler deadline (None = all);
    # a standby broker address+name enables member-driven failover.
    min_quorum: Optional[int] = None
    straggler_timeout: Optional[float] = None
    # When False, the step blocks on the gradient reduction result right
    # after contributing — comms deliberately serialized onto the
    # critical path. The default pipelines the reduction under the next
    # rollout; stepscope's exposed_comms_fraction is exactly the gauge
    # that tells these two modes apart (docs/observability.md).
    overlap_comms: bool = True
    broker: Optional[str] = None  # None -> start an in-process broker
    broker_standby: Optional[str] = None  # standby broker address
    broker_standby_name: str = "broker2"
    group: str = "a2c"
    log_interval_steps: int = 4_000
    seed: int = 0

    @classmethod
    def from_fleet_spec(cls, spec, **overrides) -> "A2CConfig":
        """Derive the launch shape from a declarative
        :class:`~moolib_tpu.fleet.spec.FleetSpec` (docs/fleet.md): the
        env tier's worker count and the learner cohort's
        quorum/straggler/group knobs come from the spec — one validated
        value drives both the fleet controller and the training
        example. Everything else keeps its default unless overridden."""
        cfg = cls(
            num_processes=max(spec.env_workers.n, 1),
            min_quorum=spec.learners.min_quorum,
            straggler_timeout=spec.learners.straggler_timeout_s,
            group=spec.learners.group,
        )
        return dataclasses.replace(cfg, **overrides) if overrides else cfg


def a2c_loss(params, apply_fn, batch, config):
    """A2C loss on a time-major unroll: n-step bootstrapped returns,
    advantage policy gradient, baseline MSE, entropy bonus (reference:
    examples/a2c.py loss math; ``config`` is an
    :class:`moolib_tpu.learner.ImpalaConfig` so this plugs into
    ``make_grad_step(loss_fn=...)``)."""
    import jax
    import jax.numpy as jnp

    (logits, baseline), _ = apply_fn(
        params, batch["obs"], batch["done"], batch["core_state"]
    )
    logits_t = logits[:-1]
    baseline_t = baseline[:-1]
    bootstrap = jax.lax.stop_gradient(baseline[-1])

    rewards = batch["rewards"][1:]
    if config.reward_clip > 0:
        rewards = jnp.clip(rewards, -config.reward_clip, config.reward_clip)
    discounts = (~batch["done"][1:]).astype(jnp.float32) * config.discounting

    def back(ret, rd):
        r, d = rd
        ret = r + d * ret
        return ret, ret

    _, returns = jax.lax.scan(
        back, bootstrap, (rewards, discounts), reverse=True
    )
    adv = jax.lax.stop_gradient(returns - baseline_t)

    logp = jax.nn.log_softmax(logits_t, axis=-1)
    action_logp = jnp.take_along_axis(
        logp, batch["actions"][..., None], axis=-1
    ).squeeze(-1)
    pg_loss = -jnp.mean(action_logp * adv)
    baseline_loss = 0.5 * jnp.mean(
        (jax.lax.stop_gradient(returns) - baseline_t) ** 2
    )
    p = jnp.exp(logp)
    entropy = -jnp.mean(jnp.sum(p * logp, axis=-1))

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
        "mean_baseline": jnp.mean(baseline_t),
    }
    return total, metrics


def train(cfg: A2CConfig, log_fn=print) -> List[dict]:
    """Train A2C on CartPole; returns the list of logged stat rows."""
    from moolib_tpu.utils import stage_host_async

    import jax
    import jax.numpy as jnp
    import optax

    from moolib_tpu.learner import (
        ImpalaConfig,
        make_act_step,
        make_apply_step,
        make_grad_step,
        make_train_state,
    )
    from moolib_tpu.models import A2CNet, ImpalaNet

    broker = None
    broker_addr = cfg.broker
    if broker_addr is None:
        broker = InProcessBroker()
        broker_addr = broker.address

    rpc = moolib_tpu.Rpc(f"a2c-{moolib_tpu.create_uid()[:8]}")
    rpc.listen("127.0.0.1:0")
    rpc.connect(broker_addr)

    if cfg.env == "cartpole":
        net = A2CNet(
            num_actions=2,
            hidden_sizes=(cfg.hidden_size, cfg.hidden_size),
            use_lstm=cfg.use_lstm,
            lstm_size=cfg.hidden_size,
        )
        dummy_obs = jnp.zeros((1, 1, 4), jnp.float32)
    else:
        # Pixel A2C (benchmark config 2): the IMPALA ResNet torso with the
        # same A2C loss/update — single-chip, no algorithmic change.
        net = ImpalaNet(
            num_actions=cfg.num_actions,
            use_lstm=cfg.use_lstm,
            compute_dtype=jnp.bfloat16
            if jax.default_backend() == "tpu"
            else jnp.float32,
        )
        dummy_obs = jnp.zeros((1, 1, 84, 84, 4), jnp.uint8)
    rng = jax.random.PRNGKey(cfg.seed)
    rng, init_rng = jax.random.split(rng)
    dummy_done = jnp.zeros((1, 1), bool)
    params = net.init(init_rng, dummy_obs, dummy_done, net.initial_state(1))
    optimizer = optax.chain(
        optax.clip_by_global_norm(cfg.grad_clip),
        optax.adam(cfg.learning_rate, eps=cfg.adam_eps),
    )
    state = make_train_state(params, optimizer)

    loss_cfg = ImpalaConfig(
        discounting=cfg.discounting,
        baseline_cost=cfg.baseline_cost,
        entropy_cost=cfg.entropy_cost,
        reward_clip=0.0,
    )
    act = make_act_step(net.apply)
    grad_step = make_grad_step(
        net.apply, config=loss_cfg, loss_fn=a2c_loss,
        grad_scale=float(cfg.batch_size),
    )
    # apply_step donates its state argument: the previous generation's
    # buffers die the moment the update is dispatched, so XLA updates in
    # place instead of holding both generations of params + opt_state.
    # The cost: get_state (Accumulator RPC threads serving requestState)
    # reads the same `state` binding, so the full-model device_get and
    # the apply+rebind must be mutually exclusive — state_lock below.
    # Lock order is always accumulator._lock -> state_lock (via the
    # callbacks); nothing under state_lock takes the accumulator's.
    apply_step = make_apply_step(optimizer, donate=True)
    state_lock = threading.Lock()

    def get_state():
        with state_lock:
            return {
                "state": jax.device_get(state),
                "model_version": accumulator.model_version,
            }

    def set_state(payload):
        nonlocal state
        with state_lock:
            state = jax.tree_util.tree_map(jnp.asarray, payload["state"])

    accumulator = moolib_tpu.Accumulator(
        rpc,
        group_name=cfg.group,
        virtual_batch_size=cfg.virtual_batch_size or cfg.batch_size,
        get_state=get_state,
        set_state=set_state,
        min_quorum=cfg.min_quorum,
        straggler_timeout=cfg.straggler_timeout,
    )
    if cfg.broker_standby:
        # Member-driven broker failover: a dark primary is written off
        # after a few ping intervals and the standby adopts the epoch
        # from cohort gossip (docs/reliability.md).
        rpc.connect(cfg.broker_standby)
        accumulator.group.set_broker_candidates(
            ["broker", cfg.broker_standby_name]
        )

    from moolib_tpu.examples.envs import make_env_fn

    pool = moolib_tpu.EnvPool(
        make_env_fn(cfg.env, num_actions=cfg.num_actions),
        num_processes=cfg.num_processes,
        batch_size=cfg.batch_size,
        num_batches=cfg.num_batches,
        action_dtype=np.int64,
    )

    stats = Stats(
        env_steps=StatSum(),
        updates=StatSum(),
        skips=StatSum(),
        dropped_unrolls=StatSum(),
        mean_episode_return=StatMean(),
        total_loss=StatMean(),
        entropy=StatMean(),
    )
    logs: List[dict] = []

    batch_states = [
        EnvBatchState(cfg.unroll_length, net.initial_state(cfg.batch_size))
        for _ in range(cfg.num_batches)
    ]
    actions = [
        np.zeros(cfg.batch_size, np.int64) for _ in range(cfg.num_batches)
    ]
    pending_unrolls: List[dict] = []
    # Device-resident metrics drained in bulk at log boundaries — no
    # blocking per-update float() on the training thread (VERDICT r4 #2).
    pending_metrics: List[dict] = []

    def drain_metrics(keep_last: int = 0):
        while len(pending_metrics) > keep_last:
            m = pending_metrics.pop(0)
            stats["total_loss"] += float(m["total_loss"])
            stats["entropy"] += float(m["entropy"])

    env_steps = 0
    next_log = cfg.log_interval_steps
    futures = [pool.step(i, actions[i]) for i in range(cfg.num_batches)]
    # Phase attribution for the learner loop (docs/observability.md,
    # "Step-phase attribution"): one ledger per while-iteration, phases
    # env_wait / host_sync / grad_dispatch / grad_allreduce /
    # apply_dispatch.
    scope = StepScope("a2c_learner")

    try:
        while env_steps < cfg.total_steps:
          with scope.step():
            for i in range(cfg.num_batches):
                # Bounded wait: a dead env worker must surface as an
                # error, not hang the training loop forever. WorkerDied is
                # the RETRY-SAFE class (pool supervision respawns the
                # worker; same-action retry is exactly-once per env), so
                # training survives an actor-process death mid-run.
                with scope.phase("env_wait"):
                    try:
                        out = futures[i].result(timeout=300.0)
                    except moolib_tpu.WorkerDied:
                        out = moolib_tpu.step_with_retry(
                            pool, i, actions[i], timeout=300.0
                        )
                bs = batch_states[i]
                unroll = bs.observe(out)
                if unroll is not None:
                    pending_unrolls.append(unroll)
                    # Backpressure: never queue stale rollouts without bound
                    # while disconnected or the learner lags.
                    while len(pending_unrolls) > 4:
                        pending_unrolls.pop(0)
                        stats["dropped_unrolls"] += 1
                rng, act_rng = jax.random.split(rng)
                a, logits, core = act(
                    state.params,
                    act_rng,
                    jnp.asarray(out["obs"]),
                    jnp.asarray(out["done"]),
                    bs.core_state,
                )
                with scope.phase("host_sync"):
                    a = np.asarray(a)  # hotlint: sync -- actions must reach the host NOW to feed the envpool slab: the Sebulba actor-loop boundary, not a stray sync
                    bs.record_action(a, np.asarray(logits), core)  # hotlint: sync -- behavior logits ride the host-side unroll buffer with the action that produced them
                actions[i][:] = a
                futures[i] = pool.step(i, actions[i])
                env_steps += cfg.batch_size
                stats["env_steps"] += cfg.batch_size

            accumulator.update()
            if accumulator.connected():
                if accumulator.wants_gradients():
                    if pending_unrolls:
                        unroll = pending_unrolls.pop(0)
                        batch = {
                            k: jnp.asarray(v) if not isinstance(v, tuple) else v
                            for k, v in unroll.items()
                        }
                        with scope.phase("grad_dispatch"):
                            grads, metrics = grad_step(state.params, batch)
                            # Defer the host readback (same as the vtrace
                            # loop): a float() here would block on device
                            # execution before reduce_gradients could even
                            # stage the async D2H.
                            pending_metrics.append(stage_host_async(metrics))
                        if len(pending_metrics) >= 64:
                            # Bound the backlog; all but the newest have had
                            # >=1 update of transfer time.
                            drain_metrics(keep_last=1)
                        # grad_scale already turned batch-mean grads into
                        # the batch-sum contribution inside the jit
                        # (Accumulator contract: src/accumulator.cc:880-1003).
                        with scope.phase("grad_allreduce"):
                            accumulator.reduce_gradients(
                                grads, batch_size=cfg.batch_size
                            )
                            if not cfg.overlap_comms:
                                # Deliberately serialized: block this step
                                # on the reduction result so the wire wait
                                # is exposed on the critical path — the
                                # measurable baseline the overlap work
                                # (ROADMAP item 4) must beat.
                                deadline = time.monotonic() + 60.0
                                while (
                                    accumulator.connected()
                                    and not accumulator.has_gradients()
                                    and time.monotonic() < deadline
                                ):
                                    accumulator.update()
                                    time.sleep(0.0005)
                    else:
                        accumulator.skip_gradients()
                        stats["skips"] += 1
                if accumulator.has_gradients():
                    with scope.phase("apply_dispatch"):
                        mean_grads, _count = accumulator.result_gradients()
                        # Atomic with the rebind: a get_state on an RPC
                        # thread between the donating dispatch and the
                        # rebind would device_get buffers the donation
                        # just invalidated.
                        with state_lock:
                            state = apply_step(
                                state,
                                jax.tree_util.tree_map(
                                    jnp.asarray, mean_grads
                                ),
                            )
                        accumulator.zero_gradients()
                    stats["updates"] += 1

            for bs in batch_states:
                for r in bs.recent_returns():
                    stats["mean_episode_return"] += r

            if env_steps >= next_log:
                next_log += cfg.log_interval_steps
                drain_metrics()
                row = dict(stats.results(), env_steps=env_steps,
                           model_version=accumulator.model_version)
                logs.append(row)
                # Scrapeable progress: the row lands in the registry too,
                # so any peer's __telemetry scrape shows training state.
                publish_metrics(row, prefix="train", example="a2c")
                log_fn(
                    "steps {env_steps:>8}  return {mean_episode_return:7.2f}  "
                    "loss {total_loss:8.4f}  entropy {entropy:6.3f}  "
                    "updates {updates:g}".format(**row)
                )
                stats["mean_episode_return"].reset()
                stats["total_loss"].reset()
                stats["entropy"].reset()
    finally:
        scope.close()
        pool.close()
        accumulator.close()
        rpc.close()
        if broker is not None:
            broker.close()
    return logs


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--total-steps", type=int, default=A2CConfig.total_steps)
    p.add_argument("--env", type=str, default=A2CConfig.env,
                   help="cartpole | synthetic | an ALE id (ALE/Pong-v5)")
    p.add_argument("--num-actions", type=int, default=A2CConfig.num_actions,
                   help="action count for pixel envs")
    p.add_argument("--batch-size", type=int, default=A2CConfig.batch_size)
    p.add_argument("--unroll-length", type=int,
                   default=A2CConfig.unroll_length)
    p.add_argument("--num-processes", type=int,
                   default=A2CConfig.num_processes)
    p.add_argument("--learning-rate", type=float,
                   default=A2CConfig.learning_rate)
    p.add_argument("--use-lstm", action="store_true")
    p.add_argument("--broker", type=str, default=None,
                   help="tcp://HOST:PORT of a running broker; default starts "
                        "one in-process")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()
    cfg = A2CConfig(
        total_steps=args.total_steps,
        env=args.env,
        num_actions=args.num_actions,
        batch_size=args.batch_size,
        unroll_length=args.unroll_length,
        num_processes=args.num_processes,
        learning_rate=args.learning_rate,
        use_lstm=args.use_lstm,
        broker=args.broker,
        seed=args.seed,
    )
    from moolib_tpu.utils.jaxenv import enable_compile_cache

    enable_compile_cache()
    train(cfg)


if __name__ == "__main__":
    main()
