"""Elastic IMPALA/V-trace training — the flagship experiment.

Capability parity with the reference's vtrace example (reference:
examples/vtrace/experiment.py — EnvPool acting with double buffering,
time-batcher → learn-batcher two-stage batching, Accumulator-driven
train/skip decisions, leader checkpointing with atomic rename + resume that
wins leader election, cluster-wide stats allreduce, yaml config with CLI
overrides; main loop at :364-529), redesigned TPU-first:

- acting and learning are jitted XLA computations; the learn step runs under
  ``shard_map`` over a ``dp`` mesh of all local devices, so the intra-host
  gradient mean rides ICI inside the step (reference reduces everything
  through the RPC tree, src/accumulator.cc:880-1033);
- the elastic cross-peer path (virtual batch, joiners/leavers, leader model
  push) is the :class:`moolib_tpu.Accumulator` over the broker group — DCN
  control plane only;
- the two batching stages are one, and a frame goes to the device once:
  the array staged for its act call is kept for its row of the learn batch
  (:class:`moolib_tpu.ops.batcher.LearnSlabs`), whose ``obs`` one program
  puts together on the device; the small leaves are written in place into
  a reusable host slab, and rollout→HBM staging is one ``jax.device_put``
  of those per learn batch + ``shard_batch``.

Run (one peer, starts its own broker):
    python -m moolib_tpu.examples.vtrace.experiment total_steps=200000
Elastic multi-peer: start ``python -m moolib_tpu.broker`` once, then any
number of peers with ``broker=tcp://HOST:4431``.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import os
import threading
import time
from typing import List, Optional

import numpy as np

import moolib_tpu
from moolib_tpu.telemetry import StepScope, global_telemetry, publish_metrics
from moolib_tpu.examples.common import EnvBatchState, StatMean, StatSum, Stats
from moolib_tpu.examples import common
from moolib_tpu.examples.common.record import TsvLogger, write_metadata
from moolib_tpu.examples import envs as env_factories

__all__ = ["VtraceConfig", "train"]


@dataclasses.dataclass
class VtraceConfig:
    """Defaults mirror the reference's config
    (reference: examples/vtrace/config.yaml)."""

    # env
    env: str = "synthetic"  # "synthetic" | "cartpole" | an ALE id
    num_actions: int = 6
    episode_length: int = 200  # synthetic env only
    # acting
    actor_batch_size: int = 32
    num_actor_processes: int = 2
    num_actor_batches: int = 2
    unroll_length: int = 20
    # learning
    learn_batch_size: int = 32  # envs per learner update (>= actor_batch_size)
    virtual_batch_size: int = 32
    # DCN pipelining: how many gradient reductions may overlap / queue
    # unapplied (reference: set_parallel_gradients); 1 = lock-step.
    parallel_gradients: int = 2
    # Leader re-pushes full state this often to heal silent drift (reference:
    # periodic model re-broadcast); None disables.
    state_broadcast_interval: Optional[float] = 600.0
    learning_rate: float = 6e-4
    grad_clip: float = 40.0
    discounting: float = 0.99
    baseline_cost: float = 0.5
    entropy_cost: float = 0.0006
    reward_clip: float = 1.0
    use_lstm: bool = False
    model: str = "auto"  # auto | mlp | resnet | transformer | decoder_lm
    # decoder_lm: a JSON file of moolib_tpu.models.lm.decoder_lm's
    # arguments (a benchmark configuration's model.kwargs); the env's
    # observation is then a token id and its action the next token.
    lm_config: Optional[str] = None
    total_steps: int = 500_000
    max_seconds: Optional[float] = None  # wall-clock stop (benchmarks)
    # infra
    broker: Optional[str] = None  # None -> in-process broker
    # Survivable training (ISSUE 11): a standby broker (address + peer
    # name) enables member-driven failover with gossip epoch adoption;
    # min_quorum commits gradient rounds with K-of-N contributions after
    # the straggler deadline instead of failing on one stalled peer.
    broker_standby: Optional[str] = None
    broker_standby_name: str = "broker2"
    min_quorum: Optional[int] = None
    straggler_timeout: Optional[float] = None
    group: str = "vtrace"
    savedir: Optional[str] = None
    # Capture an XLA trace of updates [10, 13) — 3 steady-state updates,
    # compilation excluded.
    profile_dir: Optional[str] = None
    wandb: bool = False  # log rows to wandb when the package is available
    wandb_project: str = "moolib_tpu"
    checkpoint_interval: float = 600.0
    checkpoint_history_interval: Optional[float] = 3600.0
    log_interval_steps: int = 10_000
    stats_interval: float = 5.0
    seed: int = 0
    compute_dtype: str = "bfloat16"

    @classmethod
    def from_fleet_spec(cls, spec, **overrides) -> "VtraceConfig":
        """Derive the launch shape from a declarative
        :class:`~moolib_tpu.fleet.spec.FleetSpec` (docs/fleet.md): the
        env tier's worker count and the learner cohort's
        quorum/straggler/group knobs come from the spec — one validated
        value drives both the fleet controller and the training
        example. Everything else keeps its default unless overridden."""
        cfg = cls(
            num_actor_processes=max(spec.env_workers.n, 1),
            min_quorum=spec.learners.min_quorum,
            straggler_timeout=spec.learners.straggler_timeout_s,
            group=spec.learners.group,
        )
        return dataclasses.replace(cfg, **overrides) if overrides else cfg


def _make_env_fn(cfg: VtraceConfig):
    # Shared factory selection ("nethack" = benchmark config 5,
    # "procgen[:name]" = config 4; real packages used when installed).
    return env_factories.make_env_fn(
        cfg.env, num_actions=cfg.num_actions,
        episode_length=cfg.episode_length,
    )


def _make_model(cfg: VtraceConfig):
    import jax.numpy as jnp

    from moolib_tpu.models import (
        A2CNet,
        ImpalaNet,
        NetHackNet,
        TransformerNet,
    )

    num_actions = 2 if cfg.env == "cartpole" else cfg.num_actions
    dtype = (
        jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
    )
    model = cfg.model
    if model == "auto":
        if cfg.env == "cartpole":
            model = "mlp"
        elif cfg.env == "nethack":
            model = "nethack"
        else:
            model = "resnet"
    if model == "mlp":
        return A2CNet(num_actions=num_actions, use_lstm=cfg.use_lstm)
    if model == "transformer":
        return TransformerNet(num_actions=num_actions, compute_dtype=dtype)
    if model == "decoder_lm":
        import json

        from moolib_tpu.models.lm import decoder_lm

        if cfg.lm_config is None:
            raise ValueError("model='decoder_lm' needs lm_config=<json file>")
        with open(cfg.lm_config) as f:
            kwargs = json.load(f)
        kwargs = kwargs.get("model", {}).get("kwargs", kwargs)
        return decoder_lm(**dict(kwargs, compute_dtype=dtype))
    if model == "nethack":
        return NetHackNet(
            num_actions=num_actions, use_lstm=cfg.use_lstm,
            compute_dtype=dtype,
        )
    if model == "resnet":
        return ImpalaNet(
            num_actions=num_actions,
            use_lstm=cfg.use_lstm,
            compute_dtype=dtype,
        )
    raise ValueError(f"unknown model {cfg.model!r}")


def train(cfg: VtraceConfig, log_fn=print) -> List[dict]:
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
        replicate_state,
    )
    from moolib_tpu.ops.batcher import LearnSlabs, stage_frame
    from moolib_tpu.parallel import GlobalStatsAccumulator, make_mesh
    from moolib_tpu.parallel.mesh import shard_batch
    from moolib_tpu.utils import Checkpointer

    # --- control plane -----------------------------------------------------
    broker = None
    broker_addr = cfg.broker
    if broker_addr is None:
        from moolib_tpu.examples.common import InProcessBroker

        broker = InProcessBroker()
        broker_addr = broker.address
    rpc = moolib_tpu.Rpc(f"vtrace-{moolib_tpu.create_uid()[:8]}")
    rpc.listen("127.0.0.1:0")
    rpc.connect(broker_addr)

    # --- model / learner ---------------------------------------------------
    import math

    devices = jax.devices()
    # dp over as many local devices as the learn batch divides across.
    dp = math.gcd(len(devices), cfg.learn_batch_size)
    mesh = make_mesh(dp=dp, devices=devices[:dp]) if dp > 1 else None

    net = _make_model(cfg)
    rng = jax.random.PRNGKey(cfg.seed)
    rng, init_rng = jax.random.split(rng)
    if cfg.env == "cartpole":
        dummy_obs = jnp.zeros((1, 1, 4), jnp.float32)
    elif cfg.env == "nethack":
        from moolib_tpu.examples.envs import SyntheticNetHack

        dummy_obs = {
            "glyphs": jnp.zeros(
                (1, 1) + SyntheticNetHack.DUNGEON_SHAPE, jnp.int16
            ),
            "blstats": jnp.zeros(
                (1, 1, SyntheticNetHack.BLSTATS_SIZE), jnp.float32
            ),
        }
    elif cfg.env == "procgen" or cfg.env.startswith("procgen:"):
        dummy_obs = jnp.zeros((1, 1, 64, 64, 3), jnp.uint8)
    else:
        dummy_obs = jnp.zeros((1, 1, 84, 84, 4), jnp.uint8)
    params = net.init(
        init_rng, dummy_obs, jnp.zeros((1, 1), bool), net.initial_state(1)
    )
    optimizer = optax.chain(
        optax.clip_by_global_norm(cfg.grad_clip),
        optax.rmsprop(cfg.learning_rate, decay=0.99, eps=0.01),
    )

    def to_devices(tree):
        """Host (or single-device) pytree -> where the jitted steps keep
        it: replicated on every chip of the dp mesh, so act / grad / apply
        never re-broadcast parameters from the first chip."""
        if mesh is None:
            return jax.tree_util.tree_map(jnp.asarray, tree)
        return replicate_state(tree, mesh)

    state = to_devices(make_train_state(params, optimizer))

    loss_cfg = ImpalaConfig(
        discounting=cfg.discounting,
        baseline_cost=cfg.baseline_cost,
        entropy_cost=cfg.entropy_cost,
        reward_clip=cfg.reward_clip,
    )
    # Phase attribution for this loop (docs/observability.md, "Step-
    # phase attribution"): every part of a turn below is an explicit
    # phase, and with it a `moolib.vtrace_learner.<phase>` span on any
    # live profiler capture, so `other` is what is truly left over. The
    # calls of the jitted steps are `act_dispatch` / `grad_dispatch` /
    # `apply_dispatch`: they time dispatch, not the device. Nothing may
    # nest inside env_wait / host_sync / grad_allreduce, which are read
    # as they stand.
    scope = StepScope("vtrace_learner")
    act = make_act_step(net.apply)
    learn_apply = net.apply
    if cfg.model == "decoder_lm":
        # The expert layers' counters ride the three-element convention
        # into the training metrics (an overflow must never be silent).
        from moolib_tpu.models.lm import learn_apply as lm_learn_apply

        learn_apply = lm_learn_apply(net)

    # grad_scale folds the x batch_size "sum contribution" scaling into the
    # jitted step, so the update loop never touches gradient values on the
    # host (VERDICT r4 #2; reference keeps this off the training thread via
    # async pinned copies, src/accumulator.cc:941-980).
    grad_step = make_grad_step(
        learn_apply, config=loss_cfg, mesh=mesh,
        grad_scale=float(cfg.learn_batch_size),
    )
    # apply_step donates its state argument: the previous generation's
    # buffers die the moment the update is dispatched, so XLA updates in
    # place instead of holding params + opt_state twice. get_state runs
    # on Accumulator RPC threads (requestState service) against the same
    # `state` binding, so the full-model device_get and the apply+rebind
    # must be mutually exclusive — state_lock below. Lock order is always
    # accumulator._lock -> state_lock; nothing under state_lock takes
    # the accumulator's lock back.
    apply_step = make_apply_step(optimizer, donate=True)
    state_lock = threading.Lock()

    # --- elasticity / persistence ------------------------------------------
    def get_state():
        with state_lock:
            return {"state": jax.device_get(state)}

    def set_state(payload):
        nonlocal state
        with state_lock:
            state = to_devices(payload["state"])

    accumulator = moolib_tpu.Accumulator(
        rpc,
        group_name=cfg.group,
        virtual_batch_size=cfg.virtual_batch_size,
        get_state=get_state,
        set_state=set_state,
        parallel_gradients=cfg.parallel_gradients,
        state_broadcast_interval=cfg.state_broadcast_interval,
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

    ckpt = None
    if cfg.savedir:
        os.makedirs(cfg.savedir, exist_ok=True)
        write_metadata(
            os.path.join(cfg.savedir, "metadata.json"),
            config=dataclasses.asdict(cfg),
            peer=rpc.get_name(),
        )
        ckpt = Checkpointer(
            os.path.join(cfg.savedir, "checkpoint.ckpt"),
            interval=cfg.checkpoint_interval,
            history_interval=cfg.checkpoint_history_interval,
        )
        saved = ckpt.load()
        if saved is not None:
            state = to_devices(saved["state"])
            # The checkpoint holder must win leader election (reference:
            # experiment.py:316-322 + set_model_version).
            accumulator.set_model_version(saved["model_version"])
            log_fn(f"resumed from {ckpt.path} at version "
                   f"{saved['model_version']}")

    # --- stats -------------------------------------------------------------
    applied_version = accumulator.model_version  # 0 or the resumed version

    stats = Stats(  # cumulative; global view via the stats allreduce
        env_steps=StatSum(),
        updates=StatSum(),
        skips=StatSum(),
        dropped_unrolls=StatSum(),
        episode_returns=StatMean(cumulative=True),
    )
    window = Stats(  # per-log-interval local view
        episode_returns=StatMean(),
        total_loss=StatMean(),
        entropy=StatMean(),
        grad_norm=StatMean(),
        sps=StatMean(),
    )
    gsa = GlobalStatsAccumulator(accumulator.group, stats)
    tsv = (
        TsvLogger(os.path.join(cfg.savedir, "logs.tsv")) if cfg.savedir else None
    )
    wandb_run = None
    if cfg.wandb:
        # Optional, like the reference's wandb hookup (reference:
        # examples/vtrace/experiment.py:269-276); absence degrades to tsv.
        try:
            import wandb

            wandb_run = wandb.init(
                project=cfg.wandb_project,
                name=rpc.get_name(),
                config=dataclasses.asdict(cfg),
            )
        except concurrent.futures.CancelledError:
            raise  # executor cancellation is control flow, not "no wandb"
        except Exception as e:
            log_fn(f"wandb disabled ({e}); logging to tsv only")
    logs: List[dict] = []
    from moolib_tpu.utils.profiling import StepWindowProfiler

    profiler = StepWindowProfiler(cfg.profile_dir)

    # --- env pool ----------------------------------------------------------
    pool = moolib_tpu.EnvPool(
        _make_env_fn(cfg),
        num_processes=cfg.num_actor_processes,
        batch_size=cfg.actor_batch_size,
        num_batches=cfg.num_actor_batches,
        action_dtype=np.int64,
    )
    # The learn batch is assembled in place: each actor batch writes its
    # unroll straight into a window of columns of a reusable
    # [T+1, learn_batch_size, ...] host slab (done, rewards, actions,
    # logits: 0.2 MB a batch), and nothing is stacked or concatenated on
    # the host when the columns fill. The observation never enters the
    # slab: a frame goes to the device once, for its act call, the slab
    # keeps that device array for the frame's row, and the batch's obs is
    # put together from the T+1 frames of each window by one program on the
    # device (LearnSlabs.stage; reference: examples/common/__init__.py:154-207
    # + Batcher, which copy a frame three times on the host and transfer it
    # twice). core_state's [B, ...] leaves are joined on axis 0.
    learn_slabs = LearnSlabs(cfg.unroll_length, cfg.learn_batch_size)
    batch_states = [
        EnvBatchState(
            cfg.unroll_length, net.initial_state(cfg.actor_batch_size),
            slabs=learn_slabs,
        )
        for _ in range(cfg.num_actor_batches)
    ]
    actions = [
        np.zeros(cfg.actor_batch_size, np.int64)
        for _ in range(cfg.num_actor_batches)
    ]
    max_ready_batches = 4  # backpressure: drop rollouts past this backlog

    env_steps = 0
    # Device-resident training metrics awaiting host readback: drained in
    # bulk at log boundaries (and bounded below) instead of a blocking
    # float() per update — the per-update host-sync stall VERDICT r4 #2
    # measured. By drain time the async copies have long completed.
    pending_metrics: list = []

    def drain_metrics(keep_last: int = 0):
        with scope.phase("metrics_drain"):
            while len(pending_metrics) > keep_last:
                m = pending_metrics.pop(0)
                window["total_loss"] += float(m["total_loss"])
                window["entropy"] += float(m["entropy"])
                window["grad_norm"] += float(m["grad_norm"])

    # One act call may be in flight: (batch, action, logits, core state),
    # dispatched, both copies out asked for, nothing of it read yet. The
    # turn dispatches the next batch's call before it comes back for this
    # one, so the chip runs one batch's act step while the host stages
    # the other's frame (PERF.md section 5, atari_loop).
    in_flight = None
    act_calls = {
        overlapped: global_telemetry().registry.counter(
            "vtrace_act_calls_total", overlapped=overlapped
        )
        for overlapped in ("0", "1")
    }

    def finish_act(call, overlapped: str):
        """Wait for an act call, write its actions and logits into row t
        of the batch's window and submit the batch's envs. ``overlapped``
        is "1" when another batch's call was dispatched meanwhile."""
        nonlocal env_steps
        i, a, logits, core = call
        bs = batch_states[i]
        with scope.phase("host_sync"):
            # Four parts, to say what the thread waits for: the device
            # (the copy in, the act step, whatever is queued ahead of
            # it), each copy out, the slab.
            with scope.part("act_wait"):
                jax.block_until_ready(a)  # hotlint: sync -- actions must reach the host NOW to feed the envpool slab: the Sebulba actor-loop boundary, not a stray sync
            with scope.part("action_readback"):
                a = np.asarray(a)  # hotlint: sync -- the action's copy out, asked for at dispatch, the act step already done
            with scope.part("logits_readback"):
                logits = np.asarray(logits)  # hotlint: sync -- behavior logits ride the host-side unroll buffer with the action that produced them
            with scope.part("unroll_write"):
                bs.record_action(a, logits, core)
        # Only now, its own act step done: the copy in of the batch's
        # frame, which reads the EnvPool's view, is over before a worker
        # writes the next frame over it.
        with scope.phase("env_submit"):
            actions[i][:] = a
            futures[i] = pool.step(i, actions[i])
        act_calls[overlapped].inc()
        env_steps += cfg.actor_batch_size
        stats["env_steps"] += cfg.actor_batch_size
        for r in bs.recent_returns():
            stats["episode_returns"] += r
            window["episode_returns"] += r

    next_log = cfg.log_interval_steps
    last_stats_enqueue = 0.0
    t_start = time.monotonic()
    last_sps_mark = (t_start, 0)
    futures = [pool.step(i, actions[i]) for i in range(cfg.num_actor_batches)]

    try:
        while env_steps < cfg.total_steps and (
            cfg.max_seconds is None
            or time.monotonic() - t_start < cfg.max_seconds
        ):
          with scope.step():
            # -- acting (double-buffered, one act call in flight) -----------
            for i in range(cfg.num_actor_batches):
                # Bounded wait: a dead env worker must surface as an
                # error, not hang the acting loop forever. WorkerDied is
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
                # The key's split is a dispatch of its own, and stays where
                # it was, ahead of the staging.
                with scope.phase("act_dispatch"):
                    rng, act_rng = jax.random.split(rng)
                # The frame's one copy in. Its batch's slab keeps obs_now
                # for T+1 turns, until the learn batch is put together from
                # it, and a worker writes the next frame over the pool's
                # view one turn from now: stage_frame's arrays own their
                # memory on every backend.
                with scope.phase("obs_stage"):
                    obs_now = stage_frame(common.obs_from_env_out(out))
                    done_now = jnp.asarray(out["done"])
                with scope.phase("unroll_cat"):
                    if bs.observe(out, obs_now):
                        # Backpressure: while disconnected/electing/syncing
                        # the learner consumes nothing — drop rollouts
                        # rather than queue stale off-policy data without
                        # bound. A dropped unroll's columns are written
                        # again.
                        keep = (
                            accumulator.connected()
                            and learn_slabs.ready() < max_ready_batches
                        )
                        bs.start_unroll(keep)
                        if not keep:
                            stats["dropped_unrolls"] += 1
                with scope.phase("act_dispatch"):
                    a, logits, core = act(
                        state.params, act_rng, obs_now, done_now,
                        bs.core_state,
                    )
                    # Both copies out asked for at once, as np.asarray on
                    # a pending array asks for one: they follow the act
                    # step with no trip to the host between them (to wait
                    # first and copy after cost 2.9% of the loop's rate:
                    # PERF.md, PR 37), and are one round trip, not two.
                    a.copy_to_host_async()
                    logits.copy_to_host_async()
                # The other batch's call, dispatched before this one: the
                # chip ran it while this batch's frame was staged.
                if in_flight is not None:
                    finish_act(in_flight, "1")
                in_flight = (i, a, logits, core)
                # Never wait for the envs of a batch whose act call is
                # still in flight, they have not been submitted: where the
                # next batch to wait for is this one there is nothing to
                # overlap with, and the call is finished at once.
                if (i + 1) % cfg.num_actor_batches == i:
                    finish_act(in_flight, "0")
                    in_flight = None

            # -- learning (Accumulator-driven) ------------------------------
            with scope.phase("acc_update"):
                accumulator.update()
            if accumulator.connected():
                if accumulator.wants_gradients():
                    if not learn_slabs.empty():
                        with scope.phase("learn_batch_get"):
                            slab = learn_slabs.get()
                        with scope.phase("learn_stage"):
                            # The slab's small leaves copied in, obs put
                            # together on the device from the frames the
                            # act calls were given; the slab goes back
                            # into use once its own leaves are copied.
                            batch = learn_slabs.stage(slab)
                            if mesh is not None:
                                batch = shard_batch(mesh, batch)
                        # No host sync between grad_step dispatch and
                        # reduce_gradients return (VERDICT r4 #2): metrics
                        # stay on device (async-staged, drained at the next
                        # log boundary) and grads are already batch-sum
                        # scaled inside the jit; reduce_gradients stages
                        # them with copy_to_host_async and defers the numpy
                        # conversion to an RPC completion thread.
                        with scope.phase("grad_dispatch"):
                            grads, metrics = grad_step(state.params, batch)
                            pending_metrics.append(stage_host_async(metrics))
                        if len(pending_metrics) >= 64:
                            # Bound the backlog; everything but the newest
                            # entry has had >=1 update of transfer time.
                            drain_metrics(keep_last=1)
                        with scope.phase("grad_allreduce"):
                            accumulator.reduce_gradients(
                                grads, batch_size=cfg.learn_batch_size
                            )
                    else:
                        accumulator.skip_gradients()
                        stats["skips"] += 1
                if accumulator.has_gradients():
                    with scope.phase("grad_result"):
                        mean_grads, _count = accumulator.result_gradients()
                        # Version label for the params apply_step produces
                        # — model_version itself can advance on RPC
                        # threads.
                        applied_version = accumulator.result_model_version()
                    # BEFORE the update: result() counts completed updates,
                    # i.e. the 0-based index of the one about to run — so
                    # the [start, stop) window captures exactly those.
                    profiler.step(int(stats["updates"].result()))
                    with scope.phase("grad_stage"):
                        mean_grads = to_devices(mean_grads)
                    # Atomic with the rebind: a get_state on an RPC thread
                    # between the donating dispatch and the rebind would
                    # device_get buffers the donation just invalidated.
                    with scope.phase("apply_dispatch"), state_lock:
                        state = apply_step(state, mean_grads)
                    with scope.phase("grad_result"):
                        accumulator.zero_gradients()
                    stats["updates"] += 1

            # -- stats / checkpoint / logs ----------------------------------
            now = time.monotonic()
            if now - last_stats_enqueue >= cfg.stats_interval:
                last_stats_enqueue = now
                gsa.enqueue_global_stats()
            if ckpt is not None and accumulator.is_leader():
                with scope.phase("checkpoint"):
                    ckpt.maybe_save(
                        lambda: {
                            "state": jax.device_get(state),
                            "model_version": applied_version,
                            "config": dataclasses.asdict(cfg),
                        }
                    )
            # A row is due by the act calls made, the one in flight with
            # them: the rows then come in the turns they came in while a
            # turn ended with every batch submitted, not a turn later (in
            # a loop whose rows and updates are as many env steps apart
            # that is the turn that has just dispatched a gradient step,
            # and the drain below would wait for it).
            acted = env_steps + (
                cfg.actor_batch_size if in_flight is not None else 0
            )
            if acted >= next_log:
                next_log += cfg.log_interval_steps
                drain_metrics()
                with scope.phase("log"):
                    t_mark, s_mark = last_sps_mark
                    window["sps"].add((env_steps - s_mark) / (now - t_mark + 1e-9))
                    last_sps_mark = (now, env_steps)
                    g = gsa.global_stats.results()
                    # The envs' own step and how long finished batches lay
                    # ready, cumulative, as the pool stamped them at the
                    # end of each env_wait: the room under the turn.
                    env_step_s, env_ready_idle_s = pool.step_times()
                    row = dict(
                        window.results(),
                        time=now,
                        env_steps=env_steps,
                        global_env_steps=g.get("env_steps", 0.0),
                        global_return=g.get("episode_returns", float("nan")),
                        updates=stats["updates"].result(),
                        skips=stats["skips"].result(),
                        dropped_unrolls=stats["dropped_unrolls"].result(),
                        env_step_s=env_step_s,
                        env_ready_idle_s=env_ready_idle_s,
                        model_version=accumulator.model_version,
                        leader=accumulator.is_leader(),
                    )
                    logs.append(row)
                    # Scrapeable progress: a __telemetry scrape of this
                    # peer's Rpc shows the same row the TSV/wandb sinks get.
                    publish_metrics(row, prefix="train", example="vtrace")
                    if tsv is not None:
                        tsv.log(row)
                    if wandb_run is not None:
                        wandb_run.log(row, step=env_steps)
                    log_fn(
                        "steps {env_steps:>9}  return {episode_returns:8.2f}  "
                        "global {global_return:8.2f}  loss {total_loss:8.4f}  "
                        "sps {sps:8.0f}  updates {updates:g}".format(**row)
                    )
                    window.reset()
    finally:
        scope.close()
        profiler.close()
        pool.close()
        accumulator.close()
        rpc.close()
        if broker is not None:
            broker.close()
        if wandb_run is not None:
            wandb_run.finish()
    return logs


def _apply_overrides(cfg: VtraceConfig, overrides: List[str]) -> VtraceConfig:
    """``key=value`` CLI overrides onto the dataclass (the reference uses
    hydra for this, examples/vtrace/experiment.py:214-224)."""
    values = dataclasses.asdict(cfg)
    for item in overrides:
        if "=" not in item:
            raise SystemExit(f"override {item!r} is not key=value")
        k, v = item.split("=", 1)
        k = k.replace("-", "_")
        if k not in values:
            raise SystemExit(f"unknown config key {k!r}")
        field_type = type(values[k]) if values[k] is not None else str
        if field_type is bool:
            values[k] = v.lower() in ("1", "true", "yes")
        elif values[k] is None:
            values[k] = v
        else:
            values[k] = field_type(v)
    return VtraceConfig(**values)


def main():
    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    p.add_argument("--config", type=str, default=None,
                   help="yaml file of VtraceConfig fields")
    p.add_argument("overrides", nargs="*",
                   help="key=value config overrides")
    args = p.parse_args()
    values = {}
    if args.config:
        import yaml

        with open(args.config) as f:
            values = yaml.safe_load(f) or {}
    cfg = _apply_overrides(VtraceConfig(**values), args.overrides)
    from moolib_tpu.utils.jaxenv import enable_compile_cache

    enable_compile_cache()
    train(cfg)


if __name__ == "__main__":
    main()
