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


# --------------------------------------- an action that is a set of tokens

def _grouped(batch, action_step, steps):
    """``batch`` [T] as a batch whose token axis is its own: the steps'
    leaves cut to ``steps`` frames, the grouping beside the actions."""
    return dict(
        batch, action_step=jnp.asarray(action_step, jnp.int32),
        done=batch["done"][:steps + 1], rewards=batch["rewards"][:steps + 1],
    )


def _token_apply(net, tokens: int):
    """``apply_fn`` in the grouped contract from the A2C net (no state, so
    ``done`` is not read): logits of the first ``tokens`` rows, the
    token-actions', and a baseline of every row."""
    def apply(p, obs, done, core_state):
        (logits, baseline), state = net.apply(
            p, obs, jnp.zeros(obs.shape[:2], bool), core_state)
        return (logits[:tokens], baseline), state

    return apply


def test_one_token_a_step_is_the_loss_there_was(net_and_params, rng):
    """Every step holding exactly one token, in order, with one bootstrap
    row: the grouped loss is today's loss on the same numbers, to float32
    rounding (1e-6: the entropy is summed token by token and then averaged
    where the other form averages once, and a step's value is a sum over
    one token divided by a count of one)."""
    net, params = net_and_params
    batch = dict(make_batch(rng), done=jnp.zeros((T + 1, B), bool))
    plain, m_plain = impala_loss(params, net.apply, batch, ImpalaConfig())
    one_each = _grouped(batch, np.tile(np.arange(T)[:, None], (1, B)), T)
    grouped, m_grouped = impala_loss(
        params, _token_apply(net, T), one_each, ImpalaConfig())
    np.testing.assert_allclose(grouped, plain, rtol=1e-6, atol=1e-6)
    for name in ("pg_loss", "baseline_loss", "entropy", "mean_baseline"):
        np.testing.assert_allclose(
            m_grouped[name], m_plain[name], rtol=1e-6, atol=1e-6)


def test_uneven_groups_against_the_equations_in_numpy(net_and_params, rng):
    """Steps of one to three tokens that lie in any order on the token
    axis, two bootstrap rows: the loss against a NumPy transcription of
    its definition (sums of log-probabilities and entropies and means of
    values over a step's tokens, ratios clipped a step, the recursion as a
    backward loop)."""
    net, params = net_and_params
    steps, tokens, cfg = 4, T - 1, ImpalaConfig()  # T + 1 rows: two last
    batch = make_batch(rng)
    # the tokens of steps 0..3, shuffled a column: sizes 1, 3, 2, 1
    step = np.stack([
        rng.permutation(np.repeat(np.arange(steps), [1, 3, 2, 1]))
        for _ in range(B)
    ], axis=1)
    grouped = dict(
        _grouped(batch, step, steps), actions=batch["actions"][:tokens],
        behavior_logits=jax.random.normal(
            jax.random.PRNGKey(5), (tokens, B, A)),
    )

    apply = _token_apply(net, tokens)
    total, metrics = impala_loss(params, apply, grouped, cfg)
    (logits, values), _ = apply(params, grouped["obs"], None, ())
    logits, values = np.asarray(logits, np.float64), np.asarray(
        values, np.float64)
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    mu = np.asarray(grouped["behavior_logits"], np.float64)
    logmu = mu - np.log(np.exp(mu).sum(-1, keepdims=True))
    a = np.asarray(grouped["actions"])
    take = lambda x: np.take_along_axis(x, a[..., None], -1)[..., 0]  # noqa
    lp, lm = take(logp), take(logmu)
    H = -(np.exp(logp) * logp).sum(-1)
    log_rho, log_pi, H_u, V = (np.zeros((steps, B)) for _ in range(4))
    for b in range(B):
        for u in range(steps):
            G = step[:, b] == u
            log_rho[u, b] = (lp - lm)[G, b].sum()
            log_pi[u, b], H_u[u, b] = lp[G, b].sum(), H[G, b].sum()
            V[u, b] = values[:tokens][G, b].mean()
    V_n = values[tokens:].mean(0)
    r = np.clip(np.asarray(grouped["rewards"], np.float64)[1:], -1, 1)
    g = (~np.asarray(grouped["done"])[1:]) * cfg.discounting
    rho = np.exp(log_rho)
    V_next = np.concatenate([V[1:], V_n[None]])
    delta = np.minimum(rho, 1) * (r + g * V_next - V)
    acc, vs = np.zeros(B), np.zeros((steps, B))
    for u in reversed(range(steps)):
        acc = delta[u] + g[u] * np.minimum(rho[u], 1) * acc
        vs[u] = V[u] + acc
    vs_next = np.concatenate([vs[1:], V_n[None]])
    adv = np.minimum(rho, 1) * (r + g * vs_next - V)
    want = (-(log_pi * adv).mean() + cfg.baseline_cost * 0.5 * (
        (vs - V) ** 2).mean() - cfg.entropy_cost * H_u.mean())
    np.testing.assert_allclose(total, want, rtol=2e-5)
    np.testing.assert_allclose(metrics["entropy"], H_u.mean(), rtol=2e-5)
    # and the gradient flows to every parameter through the group sums
    grads = jax.grad(lambda p: impala_loss(p, apply, grouped, cfg)[0])(params)
    assert all(float(jnp.max(jnp.abs(x))) > 0
               for x in jax.tree_util.tree_leaves(grads))


def test_a_batch_without_the_grouping_never_reaches_its_code(
        net_and_params, rng, monkeypatch):
    """The nine learner cells' batches carry no ``action_step``: with the
    grouping's functions replaced by ones that raise, the IMPALA net's
    loss and a decoder's (``mellum2_tiny``'s shape: next-token actions,
    causal kinds) trace and run as before."""
    from moolib_tpu.models.lm import decoder_lm, learn_apply
    from moolib_tpu.ops import vtrace

    def never(*args, **kwargs):
        raise AssertionError("the grouping's code was reached")

    monkeypatch.setattr(vtrace, "from_grouped_logits", never)
    monkeypatch.setattr(vtrace, "group_sum", never)
    net, params = net_and_params
    loss, _ = impala_loss(params, net.apply, make_batch(rng), ImpalaConfig())
    assert np.isfinite(float(loss))
    lm_net = decoder_lm(
        vocab_size=64, hidden_size=32,
        layers=[{"attention": "sliding", "mlp": "sparse"},
                {"attention": "full", "mlp": "sparse"}],
        attention_kinds={
            "sliding": {"window": 8, "rope": {"theta": 500000.0}},
            "full": {"window": None, "rope": {"theta": 500000.0}},
        },
        num_heads=4, num_kv_heads=1, head_dim=16, num_experts=8, top_k=2,
        moe_intermediate_size=24, experts_held=[2, 4],
    )
    obs = jax.random.randint(jax.random.PRNGKey(1), (T + 1, 2), 0, 64)
    batch = {
        "obs": obs, "done": jnp.zeros((T + 1, 2), bool).at[3].set(True),
        "rewards": jnp.ones((T + 1, 2)), "actions": obs[1:],
        "behavior_logits": jnp.zeros((T, 2, 64)), "core_state": (),
    }
    lm_params = lm_net.init(jax.random.PRNGKey(0), obs, batch["done"], ())
    loss, metrics = jax.jit(
        lambda p, b: impala_loss(p, learn_apply(lm_net), b, ImpalaConfig())
    )(lm_params, batch)
    assert np.isfinite(float(loss)) and "moe_assignments_held" in metrics


@pytest.mark.parametrize("leaf,shape,says", [
    ("actions", (T - 1, B), r"actions \(7, 16\)"),
    ("rewards", (T, B), r"rewards \(8, 16\)"),
    ("behavior_logits", (T + 1, B, A), r"behavior_logits \(9, 16, 3\)"),
    ("done", (T + 2, B), r"done \(10, 16\)"),
    ("action_step", (T, B), r"action_step \(8, 16\)"),
])
def test_leaves_that_disagree_on_their_axes_say_so(net_and_params, rng, leaf,
                                                   shape, says):
    """A batch whose leaves disagree on their axes' lengths fails where
    the loss is traced, with the lengths in the message, and not as a
    broadcast."""
    net, params = net_and_params
    batch = make_batch(rng)
    apply = net.apply
    if leaf == "action_step":  # T token-actions need a baseline of T + K
        batch = dict(batch, obs=batch["obs"][:T])
    batch[leaf] = jnp.zeros(shape, batch.get(leaf, jnp.zeros((), jnp.int32)
                                             ).dtype)
    with pytest.raises(ValueError, match=says):
        jax.jit(lambda p, b: impala_loss(p, apply, b, ImpalaConfig()))(
            params, batch)
