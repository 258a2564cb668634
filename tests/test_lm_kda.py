"""The gated delta rule in chunks (``ops/delta_rule.py``) against the
recurrence one position at a time; the decoder with delta-rule blocks, a
softmax block without rotary and with an output gate, and a state handed
from call to call (``models/lm.py``) against its plain reference
(``benchmark/reference/solar_open2_share8.py``: float32 ``jax.numpy`` from
the equations, the recurrence position by position, nothing of the
program), on the CPU at tiny sizes with seeded weights; and the
benchmark's configuration at its published widths."""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.lib import counts_kda, program, reference_latent  # noqa: E402
from benchmark.lib import reference_train, seeded_kda  # noqa: E402
from benchmark.reference import solar_open2_share8 as reference  # noqa: E402
from benchmark.reference import solar_open2_tiny  # noqa: E402
from moolib_tpu.learner import (ImpalaConfig, impala_loss,  # noqa: E402
                                make_act_step, make_impala_train_step,
                                make_train_state)
from moolib_tpu.models import lm  # noqa: E402
from moolib_tpu.models.lm import decoder_lm, learn_apply  # noqa: E402
from moolib_tpu.ops import delta_rule  # noqa: E402
from moolib_tpu.ops.delta_rule import gated_delta_rule  # noqa: E402
from moolib_tpu.telemetry import global_telemetry  # noqa: E402

VOCAB, B = 64, 2
LOSS = {"discounting": 0.99, "baseline_cost": 0.5, "entropy_cost": 0.0006,
        "reward_clip": 1.0}
SEEDING = {"correction_bias_scale": 0.05, "conv_scale": 0.5,
           "a_log_mean": 1.4, "a_log_scale": 0.5, "dt_bias_mean": -5.0,
           "dt_bias_scale": 1.5, "state_scale": 0.1, "rows_scale": 1.0}
OPTIMIZER = {"grad_clip": 40.0, "learning_rate": 0.0006, "decay": 0.99,
             "eps": 0.01}
CAST = reference_train.identity_cast
DELTA = {"num_heads": 2, "head_dim": 16, "conv_size": 4, "gate_rank": 16,
         "allow_neg_eigval": True}
KINDS = {
    "gqa": {"window": None, "rope": None, "output_gate": True},
    "kda": {"window": None, "rope": None, "delta": DELTA},
}
MODEL = dict(
    vocab_size=VOCAB, hidden_size=32,
    layers=[{"attention": "gqa", "mlp": "sparse"},
            {"attention": "kda", "mlp": "sparse", "repeat": 3}],
    attention_kinds=KINDS, num_heads=2, num_kv_heads=1, head_dim=16,
    num_experts=16, experts_held=[4, 4], top_k=2, moe_intermediate_size=24,
    router={"scoring": "sigmoid", "selection_bias": True, "gate_scale": 1.0},
    shared_expert_size=24, rms_norm_eps=1e-5, attention_backend="dense",
    remat_blocks="input",
)
# a stack of delta-rule blocks alone: what a state can carry, it carries
# (the softmax block's context is the unroll: the repo has no cache)
CARRIED = dict(MODEL, layers=[{"attention": "kda", "mlp": "sparse"},
                              {"attention": "kda", "mlp": "sparse",
                               "repeat": 2}])


def close(a, b, tol=2e-4):
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol)


# ---------------------------------------------------------------- the op

def one_position_at_a_time(q, k, v, g, beta, seg, state, scale):
    """The rule as written, one batch row and head: ``q``, ``k``, ``g`` [T,
    Dk], ``v`` [T, Dv], ``beta`` [T], ``seg`` [T], ``state`` [Dk, Dv]."""
    def position(carry, x):
        S, episode = carry
        q_t, k_t, v_t, g_t, beta_t, seg_t = x
        S = jnp.where(seg_t == episode, S, 0.0)
        S = jnp.exp(g_t)[:, None] * S
        S = S + beta_t * jnp.outer(k_t, v_t - S.T @ k_t)
        return (S, seg_t), scale * (S.T @ q_t)

    (S, _), o = jax.lax.scan(
        position, (state, jnp.zeros((), seg.dtype)), (q, k, v, g, beta, seg))
    return o, S


def recurrence(*args):
    heads = jax.vmap(one_position_at_a_time,
                     in_axes=(0, 0, 0, 0, 0, None, 0, None))
    return jax.vmap(heads, in_axes=(0, 0, 0, 0, 0, 0, 0, None))(*args)


def rule_inputs(seed, T, Dk=16, Dv=8, heads=2, decay=1.0, beta_shift=0.0,
                done_at=(), state_scale=0.0):
    """``decay``: the size of ``g`` a position; ``beta_shift``: added to
    ``beta``'s logit (6: within 0.005 of 2); ``done_at``: (row, position)
    of every boundary."""
    r = np.random.default_rng(seed)

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = unit(r.normal(size=(B, heads, T, Dk)))
    k = unit(r.normal(size=(B, heads, T, Dk)))
    v = r.normal(size=(B, heads, T, Dv))
    g = -decay * np.abs(r.normal(size=(B, heads, T, Dk)))
    beta = 2 / (1 + np.exp(-r.normal(size=(B, heads, T)) - beta_shift))
    done = np.zeros((B, T), bool)
    for row, t in done_at:
        done[row, t] = True
    state = state_scale * r.normal(size=(B, heads, Dk, Dv))
    f32 = [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)]
    return (*f32, jnp.asarray(np.cumsum(done, 1), jnp.int32),
            jnp.asarray(state, jnp.float32))


RULE_CASES = {
    "one_episode": dict(T=128),
    "a_call_of_five_positions": dict(T=5, state_scale=0.5),
    "a_length_no_chunk_divides": dict(
        T=100, done_at=[(0, 37), (1, 64), (1, 99)]),
    "boundaries_inside_a_chunk": dict(
        T=192, done_at=[(0, 70), (0, 75), (1, 100)], state_scale=0.5),
    "boundaries_on_a_chunks_edge": dict(
        T=192, done_at=[(0, 64), (0, 128), (1, 0), (1, 191)],
        state_scale=0.5),
    "strong_decay": dict(T=128, decay=12.0, done_at=[(0, 90)],
                         state_scale=0.5),
    "beta_near_two": dict(T=128, decay=0.05, beta_shift=6.0,
                          state_scale=0.3),
    "a_state_in_and_out": dict(T=64, state_scale=1.0),
}


@pytest.mark.parametrize("case", list(RULE_CASES))
def test_the_chunked_rule_is_the_recurrence(case):
    """Outputs, the state handed on, and the gradient of every input, the
    state's too, against one position at a time."""
    args = rule_inputs(3, **RULE_CASES[case])
    seg = args[5]
    weights = jax.random.normal(jax.random.PRNGKey(1), args[2].shape)

    def total(fn, q, k, v, g, beta, state):
        o, S = fn(q, k, v, g, beta, seg, state)
        return jnp.sum(o * weights) + jnp.sum(S * S), (o, S)

    def stepwise(*a):
        return recurrence(*a, args[0].shape[-1] ** -0.5)

    inputs = args[:5] + args[6:]
    with jax.default_matmul_precision("highest"):
        (_, (o, S)), grads = jax.jit(jax.value_and_grad(
            lambda *a: total(gated_delta_rule, *a), argnums=range(6), has_aux=True
        ))(*inputs)
        (_, (want_o, want_S)), want = jax.jit(jax.value_and_grad(
            lambda *a: total(stepwise, *a), argnums=range(6), has_aux=True
        ))(*inputs)
    close(o, want_o, 5e-5)
    close(S, want_S, 5e-5)
    for name, got, ref in zip("q k v g beta state".split(), grads, want):
        assert bool(jnp.all(jnp.isfinite(got))), name
        scale = float(jnp.max(jnp.abs(ref))) + 1e-6
        assert float(jnp.max(jnp.abs(got - ref))) <= 2e-4 * scale, name
    if case == "strong_decay":
        # exp(-G) of these sums is past float32: the factored form of the
        # pair decays would have been inf * 0
        assert float(delta_rule.log_decay_min(args[3])) < -200
    if case == "beta_near_two":
        assert float(jnp.median(args[4])) > 1.99


def _scan_lengths(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(eqn.params["length"])
        assert eqn.primitive.name != "while", eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                if hasattr(sub, "jaxpr"):
                    _scan_lengths(sub.jaxpr, found)
                elif hasattr(sub, "eqns"):
                    _scan_lengths(sub, found)
    return found


@pytest.mark.parametrize("T", [4096, 100])
def test_no_loop_over_positions_forward_or_backward(T):
    """The longest chain of dependent iterations the rule adds is one step
    a chunk, forward and in the transposed pass: the traced program of
    value and gradient holds two scans of ``T / 64`` steps (``ceil``) and
    no other loop, and the program lowered from it as many ``while`` ops."""
    args = tuple(
        jax.ShapeDtypeStruct(x.shape, x.dtype) for x in rule_inputs(0, T)
    )

    def total(q, k, v, g, beta, seg, state):
        o, S = gated_delta_rule(q, k, v, g, beta, seg, state)
        return jnp.sum(o) + jnp.sum(S)

    grad = jax.value_and_grad(total, argnums=(0, 1, 2, 3, 4, 6))
    lengths = _scan_lengths(jax.make_jaxpr(grad)(*args).jaxpr, [])
    assert lengths == [-(-T // delta_rule.CHUNK)] * 2
    text = jax.jit(grad).lower(*args).as_text()
    assert text.count("stablehlo.while") == 2


def test_a_call_is_counted_where_it_is_traced():
    registry = global_telemetry().registry

    def calls():
        return registry.value(
            "recurrent_mix_calls_traced_total", path="chunked") or 0

    args = rule_inputs(0, 32)
    fn = jax.jit(gated_delta_rule)
    before = calls()
    fn(*args)
    fn(*args)  # compiled: not traced again
    assert calls() - before == 1


# ------------------------------------------------------------- the model

def tiny(model=MODEL, **over):
    model = dict(model, **over)
    return decoder_lm(**model), model


def inputs(net, model, seed, steps, done_at, columns=B):
    """``steps`` = T + 1 positions, a boundary at each of ``done_at`` in
    every column, seeded weights and a seeded, non-zero ``core_state``."""
    params = seeded_kda.make_params(
        seeded_kda.param_shapes(net), seed, model, SEEDING)
    config = {"num_actions": VOCAB, "seeding": SEEDING,
              "observation": {"vocab": VOCAB, "zipf_s": 1.0},
              "model": {"kwargs": model}}
    batch = seeded_kda.make_learn_batch(
        seed, config, steps - 1, columns, 0.0,
        jax.eval_shape(lambda: net.initial_state(columns)))
    done = np.zeros((steps, columns), bool)
    for t in done_at:
        done[t, :] = True
    return params, dict(batch, done=jnp.asarray(done))


@functools.lru_cache(maxsize=None)
def _jitted(net):
    return jax.jit(learn_apply(net))


def program_forward(net, params, batch):
    with jax.default_matmul_precision("highest"):
        return _jitted(net)(
            params, batch["obs"], batch["done"], batch["core_state"])


# T + 1, and the boundaries: inside a chunk of the recurrence, on its edge
# (64), at the first position (the state handed in is dropped), none
SIZES = {"one_chunk": (40, (13, 27)), "three_chunks": (136, (64, 100)),
         "done_at_the_first_position": (72, (0, 30)),
         "one_episode": (72, ())}


@pytest.mark.parametrize("size", list(SIZES))
def test_logits_baseline_and_state_match_the_reference(size):
    net, model = tiny()
    params, batch = inputs(net, model, 7, *SIZES[size])
    (logits, baseline), state, aux = program_forward(net, params, batch)
    with jax.default_matmul_precision("highest"):
        want_logits, want_baseline, want_state = jax.jit(
            lambda *a: solar_open2_tiny.forward(*a, CAST)
        )(params, batch["obs"], batch["done"], batch["core_state"])
    assert logits.shape == (SIZES[size][0], B, VOCAB)
    close(logits, want_logits)
    close(baseline, want_baseline)
    assert [s.shape for s in state] == [(B, 3, 2, 16, 16), (B, 3, 3, 96)]
    for got, want in zip(state, want_state):
        close(got, want, 5e-5)
    done = np.asarray(batch["done"])
    counted = counts_kda.boundary_counts(model, done[:, 0])
    for name, value in counted.items():
        assert float(aux[name]) == B * value, name
    assert float(aux["kda_log_decay_min"]) < 0
    close(aux["kda_state_rms"], jnp.sqrt(jnp.mean(state[0] ** 2)), 1e-5)


@pytest.mark.parametrize("size", ["three_chunks",
                                  "done_at_the_first_position"])
def test_loss_and_every_gradient_leaf_match_the_reference(size):
    net, model = tiny()
    params, batch = inputs(net, model, 11, *SIZES[size])
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.jit(jax.value_and_grad(
            lambda p, b: impala_loss(
                p, learn_apply(net), b, ImpalaConfig(**LOSS)),
            has_aux=True,
        ))(params, batch)
        (want, _), want_grads = jax.jit(jax.value_and_grad(
            lambda p, b: solar_open2_tiny.loss_fn(p, b, LOSS, CAST),
            has_aux=True,
        ))(params, batch)
    close(loss, want, 1e-5)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.max(jnp.abs(w))) + 1e-6
        assert float(jnp.max(jnp.abs(g - w))) <= 3e-4 * scale, name
        # the selection bias takes no gradient; every other leaf does
        if not name.endswith("['e_score_correction_bias']"):
            assert float(jnp.max(jnp.abs(w))) > 0, name


def test_three_rmsprop_steps_match_the_reference():
    """The step the benchmark times, through its first three updates,
    against the reference's loss, clip and RMSProp: the four numbers the
    cell's ``correct`` is decided by."""
    net, model = tiny()
    params, batch = inputs(net, model, 13, *SIZES["three_chunks"])
    config = {"optimizer": OPTIMIZER, "loss": LOSS}
    optimizer = program.build_optimizer(config)
    step = make_impala_train_step(
        learn_apply(net), optimizer, ImpalaConfig(**LOSS), mesh=None,
        donate=False,
    )

    def recorded(state, batch):
        state, metrics = step(state, batch)
        return state, dict(metrics, mtp_loss=0.0)

    with jax.default_matmul_precision("highest"):
        _, first = reference_latent.program_first_steps(
            recorded, make_train_state(params, optimizer), batch, 3,
            OPTIMIZER["decay"],
        )
    follower = reference_latent.Follower(solar_open2_tiny.loss_fn, config)
    reference_side = follower.follow(
        lambda: jax.tree_util.tree_map(jnp.copy, params), batch, 3,
        against=first["grad_abs"])
    numbers = reference_latent.numbers(first, reference_side)
    assert max(numbers.values()) < 1e-4, numbers


def test_the_state_handed_in_is_read_until_the_first_boundary():
    """Another state changes what the first episode's positions give and
    nothing after the boundary; with ``done`` at the first position it
    changes nothing at all."""
    net, model = tiny()
    steps, boundary = 72, 30
    params, batch = inputs(net, model, 17, steps, (boundary,))
    other = tuple(2.0 * s for s in batch["core_state"])
    (a, _), _, _ = program_forward(net, params, batch)
    (b, _), _, _ = program_forward(net, params, dict(batch, core_state=other))
    assert float(jnp.max(jnp.abs(a[:boundary] - b[:boundary]))) > 1e-3
    close(a[boundary:], b[boundary:], 1e-5)
    dropped = np.asarray(batch["done"]).copy()
    dropped[0] = True
    batch = dict(batch, done=jnp.asarray(dropped))
    (a, _), _, _ = program_forward(net, params, batch)
    (b, _), _, _ = program_forward(net, params, dict(batch, core_state=other))
    close(a, b, 1e-5)


def test_one_call_equals_a_call_a_position_and_two_unrolls():
    """The act path's contract, on a stack of delta-rule blocks: ``T``
    calls of one position through ``make_act_step`` with the state
    carried, and two unrolls with the state handed over, give what one
    call over ``T`` gives, logits and state."""
    net, model = tiny(CARRIED, remat_blocks=False)
    steps = 40
    params, batch = inputs(net, model, 19, steps, (9, 23, 24))
    obs, done, state = batch["obs"], batch["done"], batch["core_state"]
    assert len(state) == 4  # two entries of the stack, two leaves each
    with jax.default_matmul_precision("highest"):
        apply = jax.jit(net.apply)
        (whole, _), handed = apply(params, obs, done, state)
        cut = 24  # a boundary at the second unroll's first position
        (early, _), middle = apply(params, obs[:cut], done[:cut], state)
        (late, _), end = apply(params, obs[cut:], done[cut:], middle)
        act = make_act_step(net.apply)
        carried, rows = state, []
        for t in range(steps):
            _, logits, carried = act(
                params, jax.random.PRNGKey(t), obs[t], done[t], carried)
            rows.append(logits)
    close(jnp.concatenate([early, late]), whole, 5e-5)
    close(jnp.stack(rows), whole, 5e-5)
    for got, other, want in zip(end, carried, handed):
        close(got, want, 5e-5)
        close(other, want, 5e-5)


def test_a_stack_without_the_rule_carries_nothing():
    net, _ = tiny(layers=[{"attention": "gqa", "mlp": "sparse", "repeat": 2}])
    assert net.initial_state(3) == ()
    obs = jnp.zeros((8, B), jnp.int32)
    done = jnp.zeros((8, B), bool)
    params = net.init(jax.random.PRNGKey(0), obs, done, ())
    (_, _), state = net.apply(params, obs, done, ())
    assert state == ()
    stateful, _ = tiny()
    assert [s.shape for s in stateful.initial_state(3)] == [
        (3, 3, 2, 16, 16), (3, 3, 3, 96)]
    assert not any(float(jnp.max(jnp.abs(s))) for s in
                   stateful.initial_state(3))


def test_the_kinds_exclude_each_other():
    bad = dict(KINDS, kda=dict(KINDS["kda"], window=8))
    net, _ = tiny(attention_kinds=bad)
    with pytest.raises(ValueError, match="no softmax"):
        net.init(jax.random.PRNGKey(0), jnp.zeros((4, 1), jnp.int32),
                 jnp.zeros((4, 1), bool), net.initial_state(1))


def test_the_softmax_kinds_with_rotary_are_as_they_were():
    """``rope`` null and ``output_gate`` are new; a kind that states its
    rotary and no gate builds the parameters it built."""
    kinds = {"full": {"window": None, "rope": {"theta": 10000.0}}}
    net, _ = tiny(attention_kinds=kinds,
                  layers=[{"attention": "full", "mlp": "sparse"}])
    shapes = seeded_kda.param_shapes(net)
    assert sorted(shapes["params"]["block_0"]["attn"]) == ["k", "o", "q", "v"]
    gated, _ = tiny(layers=[{"attention": "gqa", "mlp": "sparse"}])
    assert sorted(
        seeded_kda.param_shapes(gated)["params"]["block_0"]["attn"]
    ) == ["gate", "k", "o", "q", "v"]


# ------------------------------------------------- a share of the layer

FULL_HEADS, SHARES = 4, 4
SPEC = dict(reference.PUBLISHED, head_dim=8, top_k=2, first_expert=0,
            query_rows=8, scan_rows=8)


def _columns(kernel, share, width):
    return kernel[..., share * width:(share + 1) * width]


@pytest.mark.parametrize("mixer", ["softmax", "delta_rule", "experts"])
def test_the_shares_add_up_to_the_uncut_layer(mixer):
    """Section 4's test: four shares of the heads (of both mixers) and
    four of the experts, through the program's own modules with a share's
    slice of the weights; what every chip computes alike (the gates' first
    factors, the output norm's gain, the router, the shared expert) whole
    in each and counted once. Their sum is the uncut reference's layer."""
    d, D, T = 32, 8, 24
    r = np.random.default_rng(5)

    def normal(*shape, scale=1.0):
        return jnp.asarray(r.normal(size=shape) * scale, jnp.float32)

    x = normal(T, 1, d)
    done = np.zeros((T, 1), bool)
    done[[7, 16]] = True
    seg_bt = jnp.asarray(np.cumsum(done, 0).T, jnp.int32)
    seg = seg_bt[0]
    with jax.default_matmul_precision("highest"):
        if mixer == "softmax":
            # 8 query heads on 4 key/value heads; a share: 2 on 1
            p = {"q": {"kernel": normal(d, 8 * D, scale=d ** -0.5)},
                 "k": {"kernel": normal(d, 4 * D, scale=d ** -0.5)},
                 "v": {"kernel": normal(d, 4 * D, scale=d ** -0.5)},
                 "gate": {"kernel": normal(d, 8 * D, scale=d ** -0.5)},
                 "o": {"kernel": normal(8 * D, d, scale=0.2)}}
            want = reference.softmax_mixer(x[:, 0], p, seg, SPEC, CAST)
            kind = lm.AttentionKind(None, None, output_gate=True)
            module = lm._Attention(kind, 2, 1, D, "dense", 16, jnp.float32)
            total = 0.0
            for s in range(SHARES):
                share = {
                    "q": {"kernel": _columns(p["q"]["kernel"], s, 2 * D)},
                    "k": {"kernel": _columns(p["k"]["kernel"], s, D)},
                    "v": {"kernel": _columns(p["v"]["kernel"], s, D)},
                    "gate": {"kernel": _columns(
                        p["gate"]["kernel"], s, 2 * D)},
                    "o": {"kernel": p["o"]["kernel"][
                        s * 2 * D:(s + 1) * 2 * D]},
                }
                total = total + module.apply(
                    {"params": share}, x, seg_bt, jnp.arange(T))[:, 0]
        elif mixer == "delta_rule":
            H, rank, K = FULL_HEADS, 8, 4
            p = {n: {"kernel": normal(d, H * D, scale=d ** -0.5)}
                 for n in ("q", "k", "v")}
            p.update({f"conv_{n}": normal(K, H * D, scale=0.5)
                      for n in ("q", "k", "v")})
            p.update({
                "f_a": {"kernel": normal(d, rank, scale=d ** -0.5)},
                "f_b": {"kernel": normal(rank, H * D, scale=rank ** -0.5)},
                "g_a": {"kernel": normal(d, rank, scale=d ** -0.5)},
                "g_b": {"kernel": normal(rank, H * D, scale=rank ** -0.5)},
                "b": {"kernel": normal(d, H, scale=d ** -0.5)},
                "A_log": normal(H), "dt_bias": normal(H * D) - 2.0,
                "o_norm": {"scale": 1.0 + normal(D, scale=0.1)},
                "o": {"kernel": normal(H * D, d, scale=0.2)},
            })
            S, rows = normal(H, D, D, scale=0.3), normal(K - 1, 3 * H * D)
            want, (want_S, want_rows) = reference.delta_mixer(
                x[:, 0], p, seg, (S, rows), SPEC, CAST)
            kind = lm.AttentionKind(None, None, delta=lm.Delta(
                1, D, K, rank, True))
            module = lm._DeltaAttention(kind, 1e-5, jnp.float32)
            total = 0.0
            for s in range(SHARES):
                share = {n: {"kernel": _columns(p[n]["kernel"], s, D)}
                         for n in ("q", "k", "v", "f_b", "g_b")}
                share.update({n: _columns(p[n], s, D) for n in (
                    "conv_q", "conv_k", "conv_v", "dt_bias")})
                share.update({
                    "f_a": p["f_a"], "g_a": p["g_a"], "o_norm": p["o_norm"],
                    "b": {"kernel": _columns(p["b"]["kernel"], s, 1)},
                    "A_log": p["A_log"][s:s + 1],
                    "o": {"kernel": p["o"]["kernel"][s * D:(s + 1) * D]},
                })
                # the rows before the convolutions: q | k | v, a head's each
                mine = jnp.concatenate([
                    _columns(part, s, D)
                    for part in jnp.split(rows, 3, axis=-1)], axis=-1)
                (y, (S_out, rows_out)), _ = module.apply(
                    {"params": share}, x, seg_bt, (S[None, s:s + 1],
                                                   mine[None]),
                    mutable=["intermediates"])
                total = total + y[:, 0]
                close(S_out[0, 0], want_S[s], 5e-5)
                close(rows_out[0], jnp.concatenate([
                    _columns(part, s, D)
                    for part in jnp.split(want_rows, 3, axis=-1)], axis=-1),
                    5e-5)
        else:
            E, f = 16, 12
            p = {"router": normal(d, E, scale=d ** -0.5),
                 "e_score_correction_bias": normal(E, scale=0.05),
                 "w_gate": normal(E, d, f, scale=d ** -0.5),
                 "w_up": normal(E, d, f, scale=d ** -0.5),
                 "w_down": normal(E, f, d, scale=f ** -0.5),
                 "shared": {n: {"kernel": normal(*shape, scale=0.2)}
                            for n, shape in (("gate", (d, f)), ("up", (d, f)),
                                             ("down", (f, d)))}}
            want = reference.experts(x[:, 0], p, SPEC, CAST)
            alike = reference.gated(x[:, 0], p["shared"], CAST)
            total = alike  # counted once
            count = E // SHARES
            for s in range(SHARES):
                module = lm._SparseMlp(
                    E, (s * count, count), 2, f, None,
                    lm.Router("sigmoid", True, 1.0), f, jnp.float32)
                share = dict(p, **{
                    n: p[n][s * count:(s + 1) * count]
                    for n in ("w_gate", "w_up", "w_down")})
                y, _ = module.apply(
                    {"params": share}, x, mutable=["intermediates"])
                total = total + y[:, 0] - alike
    assert float(jnp.max(jnp.abs(want))) > 0.1
    close(total, want, 5e-5)


# ------------------------------------------- the benchmark's configuration

def _config():
    with open(os.path.join(
            REPO, "benchmark", "configs", "solar_open2_share8.json")) as f:
        return json.load(f)


def test_the_configuration_is_the_published_model_cut_as_it_says():
    config = _config()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "Solar-Open2-250B")
    assert config["source"] == row["source_url"]
    differs = {
        k for k, v in row["config"].items() if config.get(k) != v
    }
    # a changed group goes by its top-level key
    assert differs == {k for k in config["reduced"] if "." not in k}
    group = dict(config["linear_attn_config"], num_heads=64)
    assert group == row["config"]["linear_attn_config"]
    for key, value in config["published"].items():
        top, _, inner = key.partition(".")
        was = row["config"][top][inner] if inner else row["config"][top]
        assert was == value, key
    model = config["model"]["kwargs"]
    delta = model["attention_kinds"]["kda"]["delta"]
    assert model["hidden_size"] == config["hidden_size"]
    assert model["head_dim"] == config["head_dim"] == delta["head_dim"]
    assert (model["num_heads"], model["num_kv_heads"]) == (
        config["num_attention_heads"], config["num_key_value_heads"])
    assert delta["num_heads"] == config["linear_attn_config"]["num_heads"]
    assert delta["conv_size"] == config["linear_attn_config"][
        "short_conv_kernel_size"]
    assert delta["allow_neg_eigval"] == config["kda_allow_neg_eigval"]
    assert model["attention_kinds"]["gqa"]["output_gate"] == config[
        "use_gqa_gate"]
    assert (model["attention_kinds"]["gqa"]["rope"] is None) == (
        not config["use_rope"])
    assert model["num_experts"] == config["router_width"] == 320
    assert model["experts_held"][1] == config["n_routed_experts"]
    assert model["top_k"] == config["num_experts_per_tok"]
    assert model["moe_intermediate_size"] == config["moe_intermediate_size"]
    assert model["shared_expert_size"] == (
        config["n_shared_experts"] * config["moe_intermediate_size"])
    assert model["router"]["gate_scale"] == config["routed_scaling_factor"]
    assert model["vocab_size"] == config["vocab_size"] == config[
        "num_actions"]
    # one whole period: the published layers 0-3, 1 softmax to 3 delta-rule
    kinds = [l["attention"] for l in model["layers"]
             for _ in range(l.get("repeat", 1))]
    assert len(kinds) == config["num_hidden_layers"] == 4
    assert [i for i, k in enumerate(kinds) if k == "gqa"] == [
        i for i in config["gqa_layers"] if i < 4]


def test_the_parameters_of_the_cut_are_counted():
    """840,876,697 held, 11.77 GB at the 14 B a parameter this repo trains
    at, from the program's shapes and from the description alone."""
    config = _config()
    net = program.build_model(config)
    shapes = seeded_kda.param_shapes(net)
    count = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert count == counts_kda.parameters(config["model"]["kwargs"])
    assert count == 840_876_697
    block = shapes["params"]["block_1"]
    mixer = sum(x.size for x in jax.tree_util.tree_leaves(block["attn"])) // 3
    assert mixer == 18_134_152
    assert sum(x.size for x in jax.tree_util.tree_leaves(
        shapes["params"]["block_0"]["attn"])) == 13_631_488
    state = net.initial_state(1)
    assert sum(s.size for s in state) == config["core_state_size"]
    assert [s.shape for s in state] == [
        (1, 3, 8, 128, 128), (1, 3, 3, 3072)]
