"""V-trace correctness vs a naive numpy oracle.

Oracle implements the IMPALA paper's eq. 1 n-step sum form directly
(a loop over s, the sum over t vectorised: O(T^2), float64), independent of
the recursion in moolib_tpu.ops.vtrace — mirroring the reference's test
approach of comparing against ground-truth math (reference:
examples/common/vtrace.py provenance).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moolib_tpu.ops import vtrace


def _oracle_vtrace(
    log_rhos, discounts, rewards, values, bootstrap_value,
    clip_rho=1.0, clip_pg_rho=1.0, lambda_=1.0, stepwise=False,
):
    """Eq. 1's n-step sums; ``stepwise`` runs the backwards recursion one
    step at a time instead, in the inputs' dtype: the form the module ran
    as a ``lax.scan``, what the log-depth form's rounding is held against."""
    T, B = rewards.shape
    rhos = np.exp(log_rhos)
    clipped = np.minimum(clip_rho, rhos) if clip_rho is not None else rhos
    cs = lambda_ * np.minimum(1.0, rhos)
    values_tp1 = np.concatenate([values[1:], bootstrap_value[None]], 0)
    deltas = clipped * (rewards + discounts * values_tp1 - values)
    a = discounts * cs
    vs = np.empty_like(values)
    if stepwise:
        acc = np.zeros_like(bootstrap_value)
        for t in reversed(range(T)):
            acc = deltas[t] + a[t] * acc
            vs[t] = values[t] + acc
    else:
        # vs_s = V_s + sum_{t>=s} (prod_{s<=i<t} gamma_i c_i) delta_t, one
        # s at a time, time on the contiguous axis
        a, deltas_bt = a.T, deltas.T
        for s in range(T):
            weights = np.cumprod(a[:, s:T - 1], axis=1)
            vs[s] = values[s] + deltas_bt[:, s] + np.sum(
                weights * deltas_bt[:, s + 1:], axis=1)
    vs_tp1 = np.concatenate([vs[1:], bootstrap_value[None]], 0)
    pg_rhos = np.minimum(clip_pg_rho, rhos) if clip_pg_rho is not None else rhos
    pg_adv = pg_rhos * (rewards + discounts * vs_tp1 - values)
    return vs, pg_adv


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("lambda_", [1.0, 0.9])
def test_from_importance_weights_matches_oracle(seed, lambda_):
    rng = np.random.default_rng(seed)
    T, B = 7, 5
    log_rhos = rng.uniform(-1.5, 1.5, (T, B))
    # Mix of mid-episode terminations (discount 0) and continuations.
    discounts = 0.99 * (rng.uniform(size=(T, B)) > 0.2)
    rewards = rng.standard_normal((T, B))
    values = rng.standard_normal((T, B))
    bootstrap = rng.standard_normal(B)

    out = vtrace.from_importance_weights(
        jnp.asarray(log_rhos), jnp.asarray(discounts), jnp.asarray(rewards),
        jnp.asarray(values), jnp.asarray(bootstrap), lambda_=lambda_,
    )
    ref_vs, ref_pg = _oracle_vtrace(
        log_rhos, discounts, rewards, values, bootstrap, lambda_=lambda_,
    )
    np.testing.assert_allclose(np.asarray(out.vs), ref_vs, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(out.pg_advantages), ref_pg, rtol=1e-5, atol=1e-5
    )


def test_no_clipping_thresholds():
    rng = np.random.default_rng(3)
    T, B = 5, 3
    args = (
        rng.uniform(-1, 1, (T, B)),
        np.full((T, B), 0.9),
        rng.standard_normal((T, B)),
        rng.standard_normal((T, B)),
        rng.standard_normal(B),
    )
    out = vtrace.from_importance_weights(
        *map(jnp.asarray, args), clip_rho_threshold=None,
        clip_pg_rho_threshold=None,
    )
    ref_vs, ref_pg = _oracle_vtrace(*args, clip_rho=None, clip_pg_rho=None)
    np.testing.assert_allclose(np.asarray(out.vs), ref_vs, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(out.pg_advantages), ref_pg, rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("thresholds", [1.0, None])
@pytest.mark.parametrize("lambda_", [1.0, 0.9])
@pytest.mark.parametrize("B", [1, 4])
@pytest.mark.parametrize("T", [4095, 8191, 16383])
def test_a_long_unroll_in_log_depth_matches_oracle(T, B, lambda_, thresholds):
    """The decoder cells' unrolls (one packed sequence, an episode's end a
    discount of 0 at 1 step in 2,048), where the recursion runs as an
    associative scan of some 14 levels."""
    rng = np.random.default_rng(T + B)
    args = (
        rng.uniform(-1.5, 1.5, (T, B)),
        0.99 * (rng.uniform(size=(T, B)) > 1 / 2048),
        rng.standard_normal((T, B)),
        rng.standard_normal((T, B)),
        rng.standard_normal(B),
    )
    args32 = [x.astype(np.float32) for x in args]
    out = vtrace.from_importance_weights(
        *map(jnp.asarray, args32), clip_rho_threshold=thresholds,
        clip_pg_rho_threshold=thresholds, lambda_=lambda_,
    )
    assert out.vs.dtype == out.pg_advantages.dtype == jnp.float32
    kw = dict(clip_rho=thresholds, clip_pg_rho=thresholds, lambda_=lambda_)
    ref = _oracle_vtrace(*(x.astype(np.float64) for x in args32), **kw)
    stepwise = _oracle_vtrace(*args32, **kw, stepwise=True)
    assert stepwise[0].dtype == np.float32
    for got, want, one_at_a_time in zip(out, ref, stepwise):
        # float32 sums regrouped over 14 levels: 1.2e-6 absolute on values
        # of 11 at these lengths, the margin the file's other tests have
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
        # and no less exact than one step at a time
        assert np.max(np.abs(got - want)) <= (
            2 * np.max(np.abs(one_at_a_time - want)) + 1e-6)


@pytest.mark.parametrize("T", [8191, 20])
def test_the_recursion_compiles_to_no_loop(T):
    """One form at every length: the ledger read 8,191 dependent iterations
    as 23.65 ms of a 212.75 ms step, and at IMPALA's 20 steps the chip read
    the log-depth form no slower than the scan it replaced."""
    B = 2
    args = [jnp.zeros((T, B), jnp.float32)] * 4 + [jnp.zeros(B, jnp.float32)]
    text = jax.jit(vtrace.from_importance_weights).lower(*args).compile().as_text()
    assert not re.search(r"\bwhile\(", text)


def test_from_logits_on_policy_is_td_lambda_like():
    """With behavior == target, rhos == 1: vs should be TD(lambda)-style."""
    rng = np.random.default_rng(4)
    T, B, A = 6, 4, 9
    logits = jnp.asarray(rng.standard_normal((T, B, A)))
    actions = jnp.asarray(rng.integers(0, A, (T, B)))
    discounts = jnp.full((T, B), 0.95)
    rewards = jnp.asarray(rng.standard_normal((T, B)))
    values = jnp.asarray(rng.standard_normal((T, B)))
    bootstrap = jnp.asarray(rng.standard_normal(B))

    out = vtrace.from_logits(
        logits, logits, actions, discounts, rewards, values, bootstrap
    )
    np.testing.assert_allclose(np.asarray(out.log_rhos), 0.0, atol=1e-6)
    ref_vs, _ = _oracle_vtrace(
        np.zeros((T, B)), np.asarray(discounts), np.asarray(rewards),
        np.asarray(values), np.asarray(bootstrap),
    )
    np.testing.assert_allclose(np.asarray(out.vs), ref_vs, rtol=1e-5, atol=1e-5)


def test_vtrace_hot_path_compiles_exactly_once():
    """Trace-hygiene pin (ISSUE 1): the V-trace target computation sits
    inside every learner step — repeated same-shape calls must compile
    once, or the train step pays an XLA compile per update."""
    from moolib_tpu.analysis import recompile_budget

    T, B = 7, 5
    rng = np.random.default_rng(0)
    f = jax.jit(vtrace.from_importance_weights)

    def args():
        return (
            jnp.asarray(rng.uniform(-1, 1, (T, B))),
            jnp.full((T, B), 0.95),
            jnp.asarray(rng.standard_normal((T, B))),
            jnp.asarray(rng.standard_normal((T, B))),
            jnp.asarray(rng.standard_normal(B)),
        )

    with recompile_budget(f, max_compiles=1, label="vtrace") as guard:
        for _ in range(3):
            out = f(*args())  # fresh values, identical shapes/dtypes
    assert guard.compiles == 1, "V-trace retraced on same shapes"
    assert out.vs.shape == (T, B)


def test_jit_and_grad_flow():
    """V-trace must be jittable and fully stop-gradient."""
    T, B = 4, 2

    def loss(values):
        out = vtrace.from_importance_weights(
            jnp.zeros((T, B)), jnp.full((T, B), 0.9), jnp.ones((T, B)),
            values, jnp.zeros(B),
        )
        return jnp.sum(out.vs)

    g = jax.jit(jax.grad(loss))(jnp.ones((T, B)))
    np.testing.assert_allclose(np.asarray(g), 0.0)
