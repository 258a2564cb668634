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


# ------------------------------------- the behaviour side's streamed pass


def _rows(draw, shape, actions, rng):
    """Logits ``[T, B, A]`` of one kind of row."""
    T, B, A = shape
    x = rng.standard_normal(shape).astype(np.float32) * 3
    if draw == "pm80":
        x = x / np.abs(x).max(axis=-1, keepdims=True) * 80
    elif draw == "equal":
        x = np.broadcast_to(x[..., :1], shape).copy()
    elif draw == "neg_inf":
        # two finite entries a row; on odd rows the action's is not one
        keep = (actions + np.arange(T * B).reshape(T, B) % 2)[..., None]
        keep = np.concatenate([keep, keep + A // 2], axis=-1) % A
        finite = np.take_along_axis(x, keep, axis=-1)
        x = np.full(shape, -np.inf, np.float32)
        np.put_along_axis(x, keep, finite, axis=-1)
    return jnp.asarray(x)


def _traced(path):
    """``vtrace_logprob_calls_traced_total{path=}`` as it stands."""
    from moolib_tpu.telemetry import global_telemetry

    return global_telemetry().registry.value(
        "vtrace_logprob_calls_traced_total", path=path) or 0


def _ulps(got, want, scale, n=2):
    """``got`` within ``n`` float32 ulps at ``scale`` of ``want``, an
    infinity where it has one."""
    got, want = np.asarray(got), np.asarray(want)
    finite = np.isfinite(want)
    np.testing.assert_array_equal(got[~finite], want[~finite])
    room = n * np.spacing(np.broadcast_to(
        np.asarray(scale, np.float32), want.shape))
    gap = np.abs(got[finite] - want[finite])
    assert (gap <= room[finite]).all(), (gap.max(), room[finite].min())


@pytest.mark.parametrize("at", ["first", "last", "edge"])
@pytest.mark.parametrize("draw", ["normal", "pm80", "neg_inf", "equal"])
@pytest.mark.parametrize("shape", [(64, 1, 1024), (37, 1, 384), (16, 4, 256)])
def test_the_streamed_pass_reads_as_log_softmax_does(shape, draw, at,
                                                     monkeypatch):
    """The one pass over the behaviour logits (Pallas' interpreter here;
    37 rows are a ragged block of eight) against ``log_softmax`` and
    ``take_along_axis`` in float32: values to 2 ulp at the row's scale, a
    ``-inf`` where the action's entry is one, the gradient with respect to
    the logits the plain path's, and ``from_logits`` compiled once."""
    T, B, A = shape
    rng = np.random.default_rng(A + len(draw))
    actions = {
        "first": np.zeros((T, B), np.int64),
        "last": np.full((T, B), A - 1),
        # either side of a 128-lane tile's edge
        "edge": 127 + np.arange(T * B).reshape(T, B) % 2,
    }[at]
    logits = _rows(draw, shape, actions, rng)
    actions = jnp.asarray(actions, jnp.int32)

    want = vtrace.action_log_probs(logits, actions)
    x = np.asarray(logits)
    scale = np.maximum(
        np.abs(np.where(np.isfinite(x), x, 0)).max(axis=-1),
        np.abs(np.where(np.isfinite(want), want, 0)),
    )
    _ulps(vtrace.streamed_action_log_probs(logits, actions), want, scale)

    weights = jnp.asarray(rng.standard_normal((T, B)), jnp.float32)
    grads = [
        jax.grad(lambda x: jnp.sum(weights * f(x, actions)))(logits)
        for f in (vtrace.streamed_action_log_probs, vtrace.action_log_probs)
    ]
    _ulps(grads[0], grads[1], np.abs(np.asarray(weights))[..., None])

    monkeypatch.setattr(vtrace, "action_logprob_path", lambda *a: "streamed")
    f = jax.jit(lambda *args: vtrace.from_logits(*args))  # its own cache
    zeros = jnp.zeros((T, B), jnp.float32)
    target = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    for _ in range(2):
        out = f(logits, target, actions, zeros + 0.9, zeros, zeros, zeros[0])
    assert f._cache_size() == 1
    _ulps(out.behavior_action_log_probs, want, scale)
    _ulps(out.target_action_log_probs,
          vtrace.action_log_probs(target, actions), 16.0)


def test_under_a_mesh_axis_the_plain_path_runs(monkeypatch):
    """``atari_learner_dp4`` runs ``from_logits`` inside a ``shard_map``.
    Its logits are kilobytes; and whatever the rule says of a shape, logits
    that vary over a mesh axis take the plain path: the streamed pass has
    met no ``shard_map`` on a chip, and Pallas' interpreter cannot run it
    there (its grid loop drops the varying axes)."""
    from jax.sharding import Mesh, PartitionSpec as P

    T, B, A = 16, 4, 256
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((T, B, A)), jnp.float32)
    actions = jnp.asarray(rng.integers(0, A, (T, B)), jnp.int32)
    zeros = jnp.zeros((T, B), jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    cols = P(None, "dp")
    monkeypatch.setattr(vtrace, "action_logprob_path", lambda *a: "streamed")
    before = {p: _traced(p) for p in ("streamed", "plain")}
    got = jax.jit(jax.shard_map(
        lambda x, a, z: vtrace.from_logits(
            x, x, a, z, z, z, z[0]).behavior_action_log_probs,
        mesh=mesh, in_specs=(cols, cols, cols), out_specs=cols,
    ))(logits, actions, zeros)
    assert {p: _traced(p) - before[p] for p in before} == {
        "streamed": 0, "plain": 1}
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(vtrace.action_log_probs(logits, actions)))


def _cell_logits():
    """Every cell of the benchmark: its behaviour logits' shape on a chip,
    read from its files, and the path the rule gives it on a TPU."""
    import json
    import os

    root = os.path.join(os.path.dirname(__file__), "..")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    streamed = {"mellum2_learner_8k", "solar2_learner_4k", "xing4_learner_4k"}
    cells = []
    for w in bench["workloads"]:
        with open(os.path.join(root, files[w["config"]])) as f:
            config = json.load(f)
        with open(os.path.join(
                root, "benchmark", "workloads", w["name"] + ".json")) as f:
            cell = json.load(f)
        # the loop's learn batch is its own file's, one unroll long
        train = cell.get("train_config", {})
        T = cell.get("unroll_length", train.get("unroll_length"))
        B = cell.get("batch_per_chip", train.get("learn_batch_size"))
        cells.append(pytest.param(
            (T, B, config["num_actions"]),
            "streamed" if w["name"] in streamed else "plain", id=w["name"],
        ))
    return cells


@pytest.mark.parametrize("shape,path", _cell_logits())
def test_the_rule_sends_each_cells_logits_its_way(shape, path, monkeypatch,
                                                  request):
    """Three cells' logits are a sequence's rows of a whole number of
    (8,128) tiles, 268-403 MB: streamed on a TPU. The six others are plain
    there (19,360 and 320 actions tile by nothing, the IMPALA-side batches
    are wide and kilobytes), and every one is plain on the CPU. A trace of
    ``from_logits`` counts the path once."""
    assert all(isinstance(n, int) and n > 0 for n in shape), shape
    assert vtrace.action_logprob_path(shape, jnp.float32) == "plain"
    T, B, A = shape
    args = [jax.ShapeDtypeStruct(s, d) for s, d in (
        (shape, jnp.float32), (shape, jnp.float32), ((T, B), jnp.int32),
        ((T, B), jnp.float32), ((T, B), jnp.float32), ((T, B), jnp.float32),
        ((B,), jnp.float32))]

    def traces():  # of one fresh trace of from_logits, by path
        before = {p: _traced(p) for p in ("streamed", "plain")}
        out = jax.eval_shape(lambda *a: vtrace.from_logits(*a), *args)
        assert out.behavior_action_log_probs.shape == (T, B)
        return {p: _traced(p) - n for p, n in before.items() if _traced(p) > n}

    assert traces() == {"plain": 1}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the pass decides between Mosaic and the interpreter where it is
    # traced: a trace made under this answer must not outlive it
    request.addfinalizer(vtrace._stream.clear_cache)
    assert vtrace.action_logprob_path(shape, jnp.float32) == path
    assert vtrace.action_logprob_path(shape, jnp.bfloat16) == "plain"
    assert traces() == {path: 1}


# --------------------------------------- an action that is a set of tokens

def test_group_sum_is_a_segment_sum_a_column():
    """Tokens of a step anywhere on the token axis, a step no token names,
    a token whose step lies outside: sums a column, zeros, dropped."""
    x = jnp.arange(12, dtype=jnp.float32).reshape(6, 2)
    step = jnp.asarray([[2, 0], [0, 0], [2, 3], [0, 9], [3, -1], [2, 3]])
    got = vtrace.group_sum(x, step, 4)
    want = np.zeros((4, 2), np.float32)
    for i in range(6):
        for b in range(2):
            if 0 <= int(step[i, b]) < 4:
                want[int(step[i, b]), b] += float(x[i, b])
    np.testing.assert_array_equal(got, want)
    wide = vtrace.group_sum(jnp.stack([x, 2 * x], -1), step, 4)
    np.testing.assert_array_equal(wide[..., 1], 2 * want)


def test_grouped_logits_on_policy_and_clipped_a_step():
    """On policy every step's log-ratio is 0 whatever its size; off policy
    the ratio that is clipped is the step's product, not the tokens': two
    tokens at ratios 2 and 1/4 make a step at 1/2, which a clip a token
    (1 and 1/4) would read as 1/4."""
    N, B, A, steps = 6, 1, 4, 3
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(N, B, A)), jnp.float32)
    actions = jnp.asarray(rng.integers(0, A, (N, B)))
    step = jnp.asarray([[0], [1], [1], [2], [2], [2]])
    common = dict(
        actions=actions, action_step=step,
        token_values=jnp.zeros((N, B)), discounts=jnp.full((steps, B), 0.9),
        rewards=jnp.ones((steps, B)), bootstrap_value=jnp.zeros((B,)),
    )
    on = vtrace.from_grouped_logits(logits, logits, **common)
    np.testing.assert_allclose(on.log_rhos, 0.0, atol=1e-6)
    logp = jax.nn.log_softmax(logits, -1)
    want = vtrace.group_sum(
        jnp.take_along_axis(logp, actions[..., None], -1)[..., 0], step,
        steps)
    np.testing.assert_allclose(on.target_action_log_probs, want, atol=1e-6)
    # off policy: the ratio that is clipped is the step's product
    behaviour = jnp.asarray(rng.normal(size=(N, B, A)), jnp.float32)
    off = vtrace.from_grouped_logits(behaviour, logits, **common)
    take = lambda x: jnp.take_along_axis(  # noqa: E731
        jax.nn.log_softmax(x, -1), actions[..., None], -1)[..., 0]
    token_log_rhos = np.asarray(take(logits) - take(behaviour))
    np.testing.assert_allclose(
        off.log_rhos[:, 0],
        [token_log_rhos[0, 0], token_log_rhos[1:3, 0].sum(),
         token_log_rhos[3:, 0].sum()], atol=1e-5)
    a_step = np.minimum(1.0, np.exp(np.asarray(off.log_rhos)))
    a_token = np.asarray(vtrace.group_sum(
        jnp.minimum(0.0, token_log_rhos), step, steps))
    assert np.abs(a_step - np.exp(a_token)).max() > 1e-2  # the two differ
    vs_next = np.concatenate([np.asarray(off.vs)[1:], np.zeros((1, B))])
    np.testing.assert_allclose(
        off.pg_advantages, a_step * (1.0 + 0.9 * vs_next), rtol=1e-5)
