"""Learner step tests: loss math, sharded-vs-single-device equivalence,
and learning on a toy contextual-bandit problem.

Mirrors the reference's strategy of driving the real training machinery in
tests (reference: test/integration/test_a2c.py asserts learning-curve
properties; test/unit tests assert mechanism correctness).
"""

import concurrent.futures
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from moolib_tpu.learner import (
    ImpalaConfig,
    impala_loss,
    make_act_step,
    make_impala_train_step,
    make_train_state,
    replicate_state,
)
from moolib_tpu.models import A2CNet
from moolib_tpu.parallel.mesh import make_mesh

T, B, F, A = 8, 16, 5, 3


def make_batch(rng):
    key = jax.random.PRNGKey(int(rng.integers(2**31)))
    ks = jax.random.split(key, 4)
    return {
        "obs": jax.random.normal(ks[0], (T + 1, B, F), jnp.float32),
        "done": jax.random.bernoulli(ks[1], 0.1, (T + 1, B)),
        "rewards": jax.random.normal(ks[2], (T + 1, B), jnp.float32),
        "actions": jax.random.randint(ks[3], (T, B), 0, A),
        "behavior_logits": jnp.zeros((T, B, A), jnp.float32),
        "core_state": (),
    }


@pytest.fixture(scope="module")
def net_and_params():
    net = A2CNet(num_actions=A, hidden_sizes=(32,))
    params = net.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, 1, F)),
        jnp.zeros((1, 1), bool),
        (),
    )
    return net, params


def test_loss_finite_and_grads_flow(net_and_params, rng):
    net, params = net_and_params
    batch = make_batch(rng)
    loss, metrics = impala_loss(params, net.apply, batch, ImpalaConfig())
    assert np.isfinite(float(loss))
    grads = jax.grad(
        lambda p: impala_loss(p, net.apply, batch, ImpalaConfig())[0]
    )(params)
    norms = [float(jnp.linalg.norm(g)) for g in jax.tree_util.tree_leaves(grads)]
    assert all(np.isfinite(n) for n in norms)
    assert sum(norms) > 0


def test_sharded_step_matches_single_device(net_and_params, rng):
    """One mesh step == one single-device step, bit-for-bit up to fp tolerance.

    This is the correctness contract of the dp data plane: sharding over the
    batch axis plus gradient mean must reproduce the unsharded update.
    """
    net, params = net_and_params
    opt = optax.sgd(1e-2)
    batch = make_batch(rng)

    step1 = make_impala_train_step(net.apply, opt, donate=False)
    state1 = make_train_state(params, opt)
    new1, m1 = step1(state1, batch)

    mesh = make_mesh()  # 8 virtual CPU devices, dp=8
    stepN = make_impala_train_step(net.apply, opt, mesh=mesh, donate=False)
    stateN = replicate_state(make_train_state(params, opt), mesh)
    newN, mN = stepN(stateN, batch)

    for a, b in zip(
        jax.tree_util.tree_leaves(new1.params),
        jax.tree_util.tree_leaves(newN.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    np.testing.assert_allclose(
        float(m1["total_loss"]), float(mN["total_loss"]), atol=1e-5
    )


def test_learns_contextual_bandit(net_and_params):
    """Policy-gradient sanity: reward=1 iff action == argmax(obs[:3]).

    After a few hundred IMPALA steps on on-policy data the greedy policy
    should pick the rewarded action nearly always.
    """
    net = A2CNet(num_actions=A, hidden_sizes=(32,))
    key = jax.random.PRNGKey(42)
    params = net.init(
        key, jnp.zeros((1, 1, F)), jnp.zeros((1, 1), bool), ()
    )
    opt = optax.adam(3e-3)
    cfg = ImpalaConfig(discounting=0.0, entropy_cost=0.001, reward_clip=0)
    step = make_impala_train_step(net.apply, opt, cfg, donate=False)
    act = make_act_step(net.apply)
    state = make_train_state(params, opt)

    @jax.jit
    def rollout(params, key):
        kobs, kact = jax.random.split(key)
        obs = jax.random.normal(kobs, (T + 1, B, F))
        (logits, _), _ = net.apply(params, obs, jnp.zeros((T + 1, B), bool), ())
        actions = jax.random.categorical(kact, logits[:-1])
        rewards_tb = (actions == jnp.argmax(obs[:-1, :, :3], -1)).astype(
            jnp.float32
        )
        rewards = jnp.concatenate([jnp.zeros((1, B)), rewards_tb], 0)
        return {
            "obs": obs,
            "done": jnp.ones((T + 1, B), bool),  # 1-step episodes
            "rewards": rewards,
            "actions": actions,
            "behavior_logits": logits[:-1],
            "core_state": (),
        }

    for i in range(300):
        key, k = jax.random.split(key)
        batch = rollout(state.params, k)
        state, metrics = step(state, batch)

    key, kobs = jax.random.split(key)
    obs = jax.random.normal(kobs, (1, 256, F))
    (logits, _), _ = net.apply(state.params, obs, jnp.zeros((1, 256), bool), ())
    acc = float(
        jnp.mean(jnp.argmax(logits[0], -1) == jnp.argmax(obs[0, :, :3], -1))
    )
    assert acc > 0.9, f"greedy accuracy {acc}"


def test_act_step_shapes(net_and_params):
    net, params = net_and_params
    act = make_act_step(net.apply)
    a, logits, st = act(
        params,
        jax.random.PRNGKey(0),
        jnp.zeros((B, F)),
        jnp.zeros((B,), bool),
        (),
    )
    assert a.shape == (B,) and logits.shape == (B, A) and st == ()


def test_lstm_model_trains_one_step():
    net = A2CNet(num_actions=A, hidden_sizes=(32,), use_lstm=True, lstm_size=16)
    params = net.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, B, F)),
        jnp.zeros((1, B), bool),
        net.initial_state(B),
    )
    opt = optax.sgd(1e-2)
    step = make_impala_train_step(net.apply, opt, donate=False)
    state = make_train_state(params, opt)
    batch = make_batch(np.random.default_rng(0))
    batch["core_state"] = net.initial_state(B)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["total_loss"]))

    # Over an 8-way mesh the [B, H] core_state shards over dp on axis 0,
    # consistent with the [T, B] batch leaves sharding on axis 1.
    mesh = make_mesh()
    stepN = make_impala_train_step(net.apply, opt, mesh=mesh, donate=False)
    stateN = replicate_state(make_train_state(params, opt), mesh)
    stateN, metricsN = stepN(stateN, batch)
    assert np.isfinite(float(metricsN["total_loss"]))


def test_apply_step_donated_path_matches_and_survives_get_state():
    """Regression pin for the donated example apply path (hotlint's
    jit-missing-donation burn-down): donate=True must produce the same
    numerics as the non-donating step, and a locked get_state-style full
    read concurrently with locked apply+rebind threading must never see
    donated (deleted) buffers. On CPU donation is a no-op, so the
    equivalence and the locking discipline are what this pins; on real
    accelerators the same code also reuses the buffers."""
    import threading

    from moolib_tpu.learner import make_apply_step

    opt = optax.sgd(0.1)
    params = {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))}
    grads = {"w": jnp.full((4, 4), 0.5), "b": jnp.ones((4,))}

    plain = make_apply_step(opt, donate=False)
    donating = make_apply_step(opt, donate=True)
    s_plain = make_train_state(params, opt)
    s_don = make_train_state(params, opt)

    state_lock = threading.Lock()
    stop = threading.Event()
    errs = []

    def get_state_loop():
        # The a2c/vtrace get_state shape: full device_get under the lock.
        while not stop.is_set():
            try:
                with state_lock:
                    jax.device_get(s_don)
            except concurrent.futures.CancelledError as e:  # pragma: no cover
                errs.append(e)
                raise  # recorded for the assertion below, never swallowed
            except Exception as e:  # pragma: no cover - failure capture
                errs.append(e)
                return

    reader = threading.Thread(target=get_state_loop)
    reader.start()
    try:
        for _ in range(20):
            s_plain = plain(s_plain, grads)
            with state_lock:
                s_don = donating(s_don, grads)
    finally:
        stop.set()
        reader.join(timeout=10)
    assert not errs, errs
    np.testing.assert_allclose(
        np.asarray(s_plain.params["w"]), np.asarray(s_don.params["w"]),
        rtol=1e-6,
    )
    assert int(s_don.step) == 20


def test_examples_thread_state_through_donating_apply(monkeypatch):
    """The a2c and vtrace learners must apply every update through a
    DONATING apply step and only while holding the state_lock that makes
    donation safe (get_state runs on RPC threads) — observed on real, tiny
    runs: a spy on make_apply_step sees the factory's arguments and, at
    each call, whether the caller holds its lock. remote_actors must stay
    non-donating — its infer() reads params outside the lock, concurrently
    with the train step."""
    import sys
    from pathlib import Path

    from moolib_tpu import learner
    from moolib_tpu.examples.a2c import A2CConfig, train as a2c_train
    from moolib_tpu.examples.vtrace.experiment import (
        VtraceConfig,
        train as vtrace_train,
    )

    real = learner.make_apply_step
    seen = []

    def spy(optimizer, **kwargs):
        apply = real(optimizer, **kwargs)
        record = {"donate": kwargs.get("donate"), "locked": []}
        seen.append(record)

        def watched(state, grads):
            lock = sys._getframe(1).f_locals["state_lock"]
            record["locked"].append(lock.locked())
            return apply(state, grads)

        return watched

    monkeypatch.setattr(learner, "make_apply_step", spy)
    quiet = lambda *a, **k: None  # noqa: E731
    a2c_train(
        A2CConfig(total_steps=1_500, batch_size=8, num_processes=1,
                  log_interval_steps=500),
        log_fn=quiet,
    )
    vtrace_train(
        VtraceConfig(env="cartpole", total_steps=1_500, actor_batch_size=8,
                     learn_batch_size=8, virtual_batch_size=8,
                     num_actor_processes=1, unroll_length=10,
                     log_interval_steps=500, stats_interval=0.2),
        log_fn=quiet,
    )
    assert len(seen) == 2, seen
    for record in seen:
        assert record["donate"] is True, record
        assert record["locked"], "no update was applied"
        assert all(record["locked"]), (
            "apply+rebind must hold state_lock on every update"
        )
    remote = (
        Path(__file__).resolve().parent.parent
        / "moolib_tpu/examples/remote_actors.py"
    ).read_text()
    assert "donate=False" in remote, (
        "remote_actors must NOT donate: infer() reads params outside "
        "the lock concurrently with the train step"
    )
