"""Remote actors, central learner: SEED-style split over the RPC plane.

The reference runs this topology with EnvStepper clients feeding a central
inference/learner peer (reference: src/env.cc multi-client serving plus
``define(batch_size=)`` dynamic batching in src/moolib.cc:433-576). Here:

- the **learner** peer owns the model and the TPU: it serves
  ``infer`` with ``define(batch_size=..., pad=True)`` so concurrent actor
  calls are stacked into ONE jitted forward (actors never hold parameters),
  and consumes complete unrolls from a ``define_queue`` into the two-stage
  Batcher feeding the jitted IMPALA/V-trace update;
- **actors** are thin: a local EnvPool for stepping, RPC calls for policy
  and for shipping unrolls. Any number may connect/leave; inference
  batching automatically right-sizes to whoever is present.

Run (one learner, then any number of actors)::

    python -m moolib_tpu.examples.remote_actors --role learner \
        --listen 0.0.0.0:4440
    python -m moolib_tpu.examples.remote_actors --role actor \
        --learner tcp://HOST:4440
"""

from __future__ import annotations

import argparse
import dataclasses
import threading
import time
from typing import List, Optional

import numpy as np

import moolib_tpu
from moolib_tpu.telemetry import publish_metrics
from moolib_tpu.examples.common import EnvBatchState
from moolib_tpu.examples.envs import make_env_fn

__all__ = ["RemoteConfig", "make_infer_fn", "run_learner", "run_actor"]


def make_infer_fn(apply_fn, get_params, seed: int, lock: threading.Lock):
    """Build the batched-inference callable ``run_learner`` serves as
    ``infer``. Factored out so the PRNG discipline is testable on its
    own: every call must sample with a FRESH subkey (split under
    ``lock`` — infer runs on RPC threads, and an unguarded
    read-modify-write of the key cell would let two concurrent calls
    sample with the same subkey), and a given ``seed`` must replay the
    same action sequence bit-for-bit."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def _infer(params, rng, obs, done):
        (logits, _), _ = apply_fn(params, obs[None], done[None], ())
        logits = logits[0]
        a = jax.random.categorical(rng, logits, axis=-1)
        return a, logits

    infer_rng = [jax.random.PRNGKey(seed)]

    def infer(obs, done):
        # Stacked across actors by define(batch_size=): obs arrives
        # [n_calls, B_env, ...]. Merge both batch dims into the model's B
        # (init used [T=1, B=1, ...], so only the last obs dims are
        # features) and unmerge the replies; pad=True keeps n_calls static
        # so the jit compiles once.
        obs = np.asarray(obs)
        done = np.asarray(done)
        n, b = done.shape
        obs2 = obs.reshape((n * b,) + obs.shape[2:])
        with lock:
            params = get_params()
            infer_rng[0], sub = jax.random.split(infer_rng[0])
        a, logits = _infer(
            params, sub, jnp.asarray(obs2), jnp.asarray(done.reshape(n * b))
        )
        a = np.asarray(a).reshape(n, b)
        logits = np.asarray(logits).reshape(n, b, -1)
        return a, logits

    return infer


@dataclasses.dataclass
class RemoteConfig:
    env: str = "cartpole"
    num_actions: int = 2
    actor_batch_size: int = 4     # envs per actor process
    num_env_processes: int = 2
    unroll_length: int = 20
    infer_batch_size: int = 8     # max actor calls stacked per forward
    learn_batch_size: int = 8     # envs per learner update
    total_updates: int = 100_000
    max_seconds: Optional[float] = None
    learning_rate: float = 6e-4
    grad_clip: float = 40.0
    log_interval: float = 5.0
    seed: int = 0


def run_learner(cfg: RemoteConfig, listen: str = "127.0.0.1:0",
                log_fn=print, ready_fn=None) -> List[dict]:
    """Serve inference + consume unrolls + train. ``ready_fn(addr)`` (if
    given) fires once every service is registered — use it to hand the
    bound address to actors race-free."""
    import jax
    import jax.numpy as jnp
    import optax

    from moolib_tpu.learner import (
        ImpalaConfig,
        make_impala_train_step,
        make_train_state,
    )
    from moolib_tpu.models import A2CNet, ImpalaNet
    from moolib_tpu.ops import Batcher

    rpc = moolib_tpu.Rpc("learner")
    rpc.listen(listen)

    if cfg.env == "cartpole":
        net = A2CNet(num_actions=2, hidden_sizes=(64, 64))
        dummy_obs = jnp.zeros((1, 1, 4), jnp.float32)
    elif cfg.env == "synthetic" or cfg.env.startswith("ALE/"):
        net = ImpalaNet(num_actions=cfg.num_actions)
        dummy_obs = jnp.zeros((1, 1, 84, 84, 4), jnp.uint8)
    else:
        # Dict-obs and non-84x84 envs belong to the vtrace experiment,
        # which has the full env->model wiring.
        raise ValueError(
            f"remote_actors supports cartpole/synthetic/ALE envs, not "
            f"{cfg.env!r}"
        )
    rng = jax.random.PRNGKey(cfg.seed)
    params = net.init(
        rng, dummy_obs, jnp.zeros((1, 1), bool), net.initial_state(1)
    )
    opt = optax.chain(
        optax.clip_by_global_norm(cfg.grad_clip),
        optax.rmsprop(cfg.learning_rate, decay=0.99, eps=0.01),
    )
    state = make_train_state(params, opt)
    state_lock = threading.Lock()
    # donate=False is deliberate, not an oversight: infer() (RPC threads)
    # snapshots `state.params` under state_lock but runs _infer AFTER
    # releasing it, concurrently with the train loop's step_fn — donating
    # position 0 would invalidate exactly the param buffers an in-flight
    # inference is still reading. The a2c/vtrace learners donate instead
    # because their only cross-thread readers (get_state) hold the lock
    # for the whole read.
    step_fn = make_impala_train_step(net.apply, opt, ImpalaConfig(),
                                     donate=False)

    infer = make_infer_fn(
        net.apply, lambda: state.params, cfg.seed + 1, state_lock
    )

    rpc.define(
        "infer", infer, batch_size=cfg.infer_batch_size, pad=True,
    )

    batcher = Batcher(
        batch_size=cfg.learn_batch_size, dim=1, dims={"core_state": 0}
    )
    unroll_q = rpc.define_queue("unroll")

    stop = threading.Event()

    def drain_unrolls():
        while not stop.is_set():
            try:
                return_cb, args, _kw = unroll_q.get(timeout=0.5)
            except TimeoutError:
                continue
            except moolib_tpu.RpcError:
                return  # queue closed
            # Backpressure: delay the ack while the learner lags — each
            # actor keeps only one un-acked ship in flight, so holding the
            # ack here bounds the Batcher backlog instead of growing it
            # without limit. wait_below wakes on actual consumption; the
            # timeout only bounds shutdown latency.
            while not batcher.wait_below(8, timeout=0.5):
                if stop.is_set():
                    break
            batcher.cat(args[0])
            return_cb(True)

    drainer = threading.Thread(target=drain_unrolls, daemon=True)
    drainer.start()

    # Announce only now: every service above is registered, so the first
    # actor request can never race define() and hit function-not-found.
    addr = rpc.debug_info()["listen"][0]
    log_fn(f"learner listening on {addr}")
    if ready_fn is not None:
        ready_fn(addr)

    logs: List[dict] = []
    updates = 0
    frames = 0
    t0 = time.monotonic()
    last_log = t0
    try:
        while updates < cfg.total_updates and (
            cfg.max_seconds is None or time.monotonic() - t0 < cfg.max_seconds
        ):
            try:
                # Blocking get with a short timeout (re-checks the stop and
                # deadline conditions) instead of an empty()+sleep poll.
                batch = batcher.get(timeout=0.1)
            except TimeoutError:
                continue
            batch = {
                k: jax.tree_util.tree_map(jnp.asarray, v)
                for k, v in batch.items()
            }
            with state_lock:
                state, metrics = step_fn(state, batch)
            updates += 1
            frames += cfg.unroll_length * cfg.learn_batch_size
            now = time.monotonic()
            if now - last_log >= cfg.log_interval:
                last_log = now
                row = {
                    "updates": updates,
                    "frames": frames,
                    "total_loss": float(metrics["total_loss"]),
                    "fps": frames / (now - t0),
                }
                logs.append(row)
                # Scrapeable progress: the learner Rpc's __telemetry
                # scrape shows loss/fps alongside the wire metrics.
                publish_metrics(row, prefix="train",
                                example="remote_actors")
                log_fn(
                    "updates {updates:>6}  frames {frames:>9}  "
                    "loss {total_loss:8.4f}  fps {fps:8.0f}".format(**row)
                )
        # Final flush: the loop only publishes on log ticks, so without
        # this a scrape after exit shows the last tick's counts, not the
        # totals the learner actually reached.
        if updates:
            now = time.monotonic()
            publish_metrics(
                {
                    "updates": updates,
                    "frames": frames,
                    "total_loss": float(metrics["total_loss"]),
                    "fps": frames / max(now - t0, 1e-9),
                },
                prefix="train", example="remote_actors",
            )
    finally:
        stop.set()
        drainer.join(timeout=5)
        rpc.close()
    return logs


def run_actor(cfg: RemoteConfig, learner_addr: str,
              max_seconds: Optional[float] = None) -> int:
    """Thin actor: local envs, remote policy. Returns env frames stepped."""
    rpc = moolib_tpu.Rpc(f"actor-{moolib_tpu.create_uid()[:8]}")
    rpc.connect(learner_addr)

    pool = moolib_tpu.EnvPool(
        make_env_fn(cfg.env, num_actions=cfg.num_actions),
        num_processes=cfg.num_env_processes,
        batch_size=cfg.actor_batch_size,
        num_batches=2,
    )
    bs = [
        EnvBatchState(cfg.unroll_length, ())
        for _ in range(2)
    ]
    actions = [
        np.zeros(cfg.actor_batch_size, np.int64) for _ in range(2)
    ]
    futures = [pool.step(i, actions[i]) for i in range(2)]
    frames = 0
    deadline = (
        None if max_seconds is None else time.monotonic() + max_seconds
    )
    pending_ship = None
    try:
        while deadline is None or time.monotonic() < deadline:
            try:
                for i in range(2):
                    # Bounded wait: a dead env worker must surface as an
                    # error here, not hang the actor forever. WorkerDied
                    # is retry-safe (supervised respawn + exactly-once
                    # same-action retry), so the actor keeps acting.
                    try:
                        out = futures[i].result(timeout=300.0)
                    except moolib_tpu.WorkerDied:
                        out = moolib_tpu.step_with_retry(
                            pool, i, actions[i], timeout=300.0
                        )
                    unroll = bs[i].observe(out)
                    if unroll is not None:
                        # Ship the completed unroll; keep at most one in
                        # flight (backpressure against a slow learner).
                        if pending_ship is not None:
                            pending_ship.result(timeout=60)
                        pending_ship = rpc.async_("learner", "unroll", unroll)
                    a, logits = rpc.sync(
                        "learner", "infer", out["obs"], out["done"]
                    )
                    bs[i].record_action(np.asarray(a), np.asarray(logits), ())
                    actions[i][:] = a
                    futures[i] = pool.step(i, actions[i])
                    frames += cfg.actor_batch_size
            except moolib_tpu.RpcError:
                break  # learner gone: stop cleanly, keep the frame count
    finally:
        pool.close()
        rpc.close()
    return frames


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--role", choices=("learner", "actor"), required=True)
    p.add_argument("--listen", default="127.0.0.1:0")
    p.add_argument("--learner", default=None,
                   help="learner address (actor role)")
    p.add_argument("--env", default="cartpole")
    p.add_argument("--num-actions", type=int, default=2)
    p.add_argument("--max-seconds", type=float, default=None)
    args = p.parse_args()
    cfg = RemoteConfig(
        env=args.env, num_actions=args.num_actions,
        max_seconds=args.max_seconds,
    )
    if args.role == "learner":
        run_learner(cfg, listen=args.listen)
    else:
        if not args.learner:
            p.error("--learner required for actor role")
        run_actor(cfg, args.learner, max_seconds=args.max_seconds)


if __name__ == "__main__":
    main()
