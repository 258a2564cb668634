"""Compressed convolutional attention, the MLP router whose state goes
through the depth, top-1 experts with a choice that is no expert, the
scaled residual skeleton and the tied head (``models/lm.py``,
``parallel/moe.py``) against their plain reference
(``benchmark/reference/zaya1_share8.py``: float32 ``jax.numpy`` from the
equations, nothing of the program), on the CPU at tiny sizes with seeded
weights; and the benchmark's configuration at its published widths. One
tiny model, compiled once, serves the cases."""

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

from benchmark.lib import counts_cca, program, reference_latent  # noqa: E402
from benchmark.lib import reference_train, seeded_cca  # noqa: E402
from benchmark.reference import zaya1_share8 as reference  # noqa: E402
from benchmark.reference import zaya1_tiny  # noqa: E402
from moolib_tpu.learner import (ImpalaConfig, impala_loss,  # noqa: E402
                                make_impala_train_step, make_train_state)
from moolib_tpu.models import lm  # noqa: E402
from moolib_tpu.models.lm import decoder_lm, learn_apply  # noqa: E402
from moolib_tpu.parallel.moe import linear_scores, moe_dropless  # noqa: E402

VOCAB, B, STEPS = 64, 2, 40
LOSS = {"discounting": 0.99, "baseline_cost": 0.5, "entropy_cost": 0.0006,
        "reward_clip": 1.0}
SEEDING = {"correction_bias_scale": 0.02, "conv0_scale": 0.5,
           "conv1_gain": 1.0, "unit_scale": 0.05, "router_out_gain": 4.0,
           "head_gain": 32 ** -0.5}
OPTIMIZER = {"grad_clip": 40.0, "learning_rate": 0.0006, "decay": 0.99,
             "eps": 0.01}
CAST = reference_train.identity_cast
ROPE = {"theta": 5e6, "partial_rotary_factor": 0.5}
KINDS = {"cca": {"window": None, "rope": ROPE,
                 "cca": {"time0": 2, "time1": 2}}}
ROUTER = {"scoring": "softmax", "selection_bias": True, "hidden_size": 16,
          "skip_choices": 1, "renormalize": False}
MODEL = dict(
    vocab_size=VOCAB, hidden_size=32,
    layers=[{"attention": "cca", "mlp": "sparse", "repeat": 3}],
    attention_kinds=KINDS, num_heads=4, num_kv_heads=2, head_dim=8,
    num_experts=4, experts_held=[0, 2], top_k=1, moe_intermediate_size=24,
    router=ROUTER, residual="scaled", tie_embeddings=True,
    rms_norm_eps=1e-5, attention_backend="dense", remat_blocks="input",
)
SPEC = zaya1_tiny.TINY


def close(a, b, tol=2e-4):
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol)


def tiny(model=MODEL, **over):
    model = dict(model, **over)
    return decoder_lm(**model), model


VARIANTS = {"stack": {}, "one_whole_layer": dict(
    layers=[{"attention": "cca", "mlp": "sparse"}], experts_held=[0, 4],
    remat_blocks=False)}


@functools.lru_cache(maxsize=None)
def _seeded(variant, seed, columns):
    """The seeded weights and batch of one variant of the tiny model: made
    once (the seeding compiles a program a call)."""
    net, model = tiny(**VARIANTS[variant])
    params = seeded_cca.make_params(
        seeded_cca.param_shapes(net), seed, model, SEEDING)
    config = {"num_actions": VOCAB,
              "observation": {"vocab": VOCAB, "zipf_s": 1.0},
              "model": {"kwargs": model}}
    return params, seeded_cca.make_learn_batch(
        seed, config, STEPS - 1, columns, 0.0)


def inputs(seed, done_at, columns=B, variant="stack"):
    """``STEPS`` = T + 1 positions, a boundary at each of ``done_at`` in
    every column, seeded weights."""
    params, batch = _seeded(variant, seed, columns)
    done = np.zeros((STEPS, columns), bool)
    for t in done_at:
        done[t, :] = True
    return params, dict(batch, done=jnp.asarray(done))


@functools.lru_cache(maxsize=None)
def _jitted(net):
    return jax.jit(learn_apply(net))


def program_forward(net, params, batch):
    with jax.default_matmul_precision("highest"):
        return _jitted(net)(params, batch["obs"], batch["done"], ())


@functools.lru_cache(maxsize=None)
def _reference_forward():
    return jax.jit(lambda *a: zaya1_tiny.forward(*a, CAST))


@functools.lru_cache(maxsize=None)
def _router_loads(net):
    return jax.jit(lm.router_loads(net))


@functools.lru_cache(maxsize=None)
def _gradients(net):
    program_side = jax.jit(jax.value_and_grad(
        lambda p, b: impala_loss(p, learn_apply(net), b, ImpalaConfig(**LOSS)),
        has_aux=True,
    ))
    reference_side = jax.jit(jax.value_and_grad(
        lambda p, b: zaya1_tiny.loss_fn(p, b, LOSS, CAST), has_aux=True,
    ))
    return program_side, reference_side


# the boundaries: where a position's first tap (and its shifted value)
# would reach the episode before, inside the unroll, at the first position
BOUNDARIES = {"at_a_first_tap": (1, 2), "inside_the_unroll": (13, 27),
              "done_at_the_first_position": (0, 30), "one_episode": ()}


@pytest.mark.parametrize("case", list(BOUNDARIES))
def test_logits_baseline_and_counters_match_the_reference(case):
    net, model = tiny()
    params, batch = inputs(7, BOUNDARIES[case])
    (logits, baseline), state, aux = program_forward(net, params, batch)
    with jax.default_matmul_precision("highest"):
        want_logits, want_baseline, _ = _reference_forward()(
            params, batch["obs"], batch["done"], ())
    assert state == () and net.initial_state(B) == ()
    assert logits.shape == (STEPS, B, VOCAB)
    close(logits, want_logits)
    close(baseline, want_baseline)
    done = np.asarray(batch["done"])
    assert float(aux["cca_taps_cut"]) == B * counts_cca.taps_cut(
        model, done[:, 0])
    loads = np.asarray(_router_loads(net)(
        params, batch["obs"], batch["done"]))
    assert loads.shape == (3, 5) and (loads.sum(axis=1) == STEPS * B).all()
    assert float(aux["moe_tokens_skipped"]) == loads[:, 4].sum() > 0
    assert float(aux["moe_assignments_held"]) == loads[:, :2].sum() > 0
    assert 1 / 5 < float(aux["moe_gate_mean"]) < 1
    assert float(aux["router_state_rms"]) > 0


@pytest.mark.parametrize("case", ["at_a_first_tap", "inside_the_unroll",
                                  "done_at_the_first_position"])
def test_loss_and_every_gradient_leaf_match_the_reference(case):
    net, model = tiny()
    params, batch = inputs(7, BOUNDARIES[case])
    program_side, reference_side = _gradients(net)
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = program_side(params, batch)
        (want, _), want_grads = reference_side(params, batch)
    close(loss, want, 1e-5)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    seen = set()
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        seen.add(name.split("']['")[-1].rstrip("']"))
        scale = float(jnp.max(jnp.abs(w))) + 1e-6
        assert float(jnp.max(jnp.abs(g - w))) <= 3e-4 * scale, name
        # the selection bias takes no gradient; every other leaf does, the
        # router's too, with one expert a token
        if name.endswith("['e_score_correction_bias']"):
            assert float(jnp.max(jnp.abs(g))) == 0.0, name
        else:
            assert float(jnp.max(jnp.abs(w))) > 0, name
            assert float(jnp.max(jnp.abs(g))) > 0, name
    assert {"router_gamma", "temperature", "conv0", "conv1", "a_r", "b_r",
            "a_y", "b_y", "embedding"} <= seen and "head" not in seen


def test_three_rmsprop_steps_match_the_reference():
    """The step the benchmark times, through its first three updates,
    against the reference's loss, clip and RMSProp: the four numbers the
    cell's ``correct`` is decided by."""
    net, model = tiny()
    params, batch = inputs(7, BOUNDARIES["inside_the_unroll"])
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
    follower = reference_latent.Follower(zaya1_tiny.loss_fn, config)
    reference_side = follower.follow(
        lambda: jax.tree_util.tree_map(jnp.copy, params), batch, 3,
        against=first["grad_abs"])
    numbers = reference_latent.numbers(first, reference_side)
    assert max(numbers.values()) < 1e-4, numbers


# --------------------------------------------------- the parts, by hand

def _episodes(T, done_at):
    done = np.zeros(T, bool)
    done[list(done_at)] = True
    return np.cumsum(done)


@pytest.mark.parametrize("done_at", [(), (1,), (0, 5, 6)])
def test_the_grouped_convolution_and_the_shift_position_by_position(done_at):
    T, G, D = 12, 3, 4
    r = np.random.default_rng(3)
    x = r.normal(size=(T, 1, G, D)).astype(np.float32)
    w = r.normal(size=(2, G, D, D)).astype(np.float32)
    seg = _episodes(T, done_at)
    want = np.zeros((T, G, D), np.float32)
    shifted = np.zeros((T, G * D), np.float32)
    for t in range(T):
        for g in range(G):
            want[t, g] = x[t, 0, g] @ w[1, g]
            if t > 0 and seg[t - 1] == seg[t]:
                want[t, g] += x[t - 1, 0, g] @ w[0, g]
        if t > 0 and seg[t - 1] == seg[t]:
            shifted[t] = x[t - 1, 0].reshape(-1)
    seg_tb = jnp.asarray(seg, jnp.int32)[:, None]
    with jax.default_matmul_precision("highest"):
        got = lm.grouped_causal_conv(jnp.asarray(x), jnp.asarray(w), seg_tb)
    close(got[:, 0], want, 1e-5)
    before = lm.previous_row(jnp.asarray(x).reshape(T, 1, G * D), seg_tb)
    close(before[:, 0], shifted, 0)
    close(reference.before(jnp.asarray(x[:, 0]), jnp.asarray(seg)).reshape(
        T, -1), shifted, 0)
    cut = 3 * (1 + sum(1 for t in done_at if t > 0))
    assert int(lm.cca_taps_cut(seg_tb.T, lm.Cca(2, 2))) == cut
    model = dict(MODEL, layers=[{"attention": "cca", "mlp": "sparse"}])
    done = np.zeros(T, bool)
    done[list(done_at)] = True
    assert counts_cca.taps_cut(model, done) == cut


def test_the_rotary_turns_the_share_of_a_head_it_is_told():
    positions = jnp.arange(6)
    whole = lm._rotary_tables(lm.Rope(theta=100.0), positions, 8)
    assert whole[0].shape == (6, 8)
    half = lm._rotary_tables(
        lm.Rope(theta=100.0, partial_rotary_factor=0.5), positions, 8)
    assert half[0].shape == (6, 4)
    # the frequencies are a head of 4's, not the first of a head of 8's
    close(half[0], lm._rotary_tables(lm.Rope(theta=100.0), positions, 4)[0], 0)
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 1, 2, 8))
    turned = lm._rotary(x, *half)
    close(turned[..., 4:], x[..., 4:], 0)
    close(turned[..., :4], lm._rotary(x[..., :4], *half), 0)
    assert float(jnp.max(jnp.abs(turned[1:, ..., :4] - x[1:, ..., :4]))) > 0.1
    close(turned, reference.rotary(
        x[:, 0], dict(SPEC, rotary_dim=4, rope_theta=100.0))[:, None], 1e-6)


def _layer_inputs(T=24, d=32, E=4, f=12, seed=5):
    r = np.random.default_rng(seed)

    def normal(*shape, scale=1.0):
        return jnp.asarray(r.normal(size=shape) * scale, jnp.float32)

    x = normal(T, d)
    scores = jax.nn.softmax(normal(T, E + 1, scale=2.0), axis=-1)
    experts = {"w_gate": normal(E, d, f, scale=d ** -0.5),
               "w_up": normal(E, d, f, scale=d ** -0.5),
               "w_down": normal(E, f, d, scale=f ** -0.5)}
    return x, scores, experts


@pytest.mark.parametrize("renormalize", [False, True])
def test_a_top1_router_learns_through_its_gate_or_not_at_all(renormalize):
    """With one expert a token the renormalised gate is 1 whatever the
    score, and the scores' gradient zero: the layer refuses it. The chosen
    score as it is gives the router its gradient; the selection bias gets
    none."""
    x, scores, experts = _layer_inputs()
    bias = jnp.asarray([0.3, -0.2, 0.1, 0.0, 0.05], jnp.float32)

    def total(scores, bias):
        y, aux = moe_dropless(
            experts, x, scores, top_k=1, skip_choices=1,
            select_bias=bias, renormalize=renormalize)
        return jnp.sum(y * y), aux

    if renormalize:
        with pytest.raises(ValueError, match="gradient is zero"):
            total(scores, bias)
        return
    (_, aux), (g_scores, g_bias) = jax.value_and_grad(
        total, argnums=(0, 1), has_aux=True)(scores, bias)
    assert float(jnp.max(jnp.abs(g_bias))) == 0.0
    assert float(jnp.max(jnp.abs(g_scores))) > 0.1
    assert "moe_gate_mean" in aux
    chosen = np.argmax(np.asarray(scores + bias), axis=-1)
    assert float(aux["moe_tokens_skipped"]) == (chosen == 4).sum() > 0
    assert (np.asarray(aux["moe_router_load"]) == np.bincount(
        chosen, minlength=5)).all()


def test_a_router_of_one_matrix_scores_as_the_layer_did():
    """``linear_scores`` is what the layer computed when the matrix was
    its own, and a layer of every choice an expert, renormalised, reports
    neither of the two counters that belong to the other kind."""
    x, _, experts = _layer_inputs()
    router = jax.random.normal(jax.random.PRNGKey(2), (x.shape[1], 4)) * 0.3
    scores = linear_scores(x, router)
    close(scores, jax.nn.softmax(x @ router, axis=-1), 1e-6)
    close(linear_scores(x, router, "sigmoid"), jax.nn.sigmoid(x @ router),
          1e-6)
    _, aux = moe_dropless(experts, x, scores, top_k=2)
    assert "moe_tokens_skipped" not in aux and "moe_gate_mean" not in aux


def test_a_token_that_chooses_no_expert_gets_the_sublayers_shift_alone():
    """The 17th column here is the 5th: with the selection bias on it,
    every token skips the experts, the expert layer gives exact zeros, and
    the skeleton adds ``a_y * b_y`` to the scaled stream and nothing
    else."""
    T, d = 10, 32
    module = lm._SparseMlp(4, (0, 2), 1, 24, None, lm.Router(**ROUTER),
                           None, jnp.float32, 1e-5)
    x = jax.random.normal(jax.random.PRNGKey(0), (T, 1, d))
    z = jnp.zeros((T, 1, 16))
    params = jax.jit(module.init)(jax.random.PRNGKey(1), x, z)["params"]
    params["e_score_correction_bias"] = jnp.asarray([0., 0., 0., 0., 10.])
    (y, z_out), sown = jax.jit(lambda p: module.apply(
        {"params": p}, x, z, mutable=["intermediates"]))(params)
    assert float(jnp.max(jnp.abs(y))) == 0.0
    counters = sown["intermediates"]["moe_counters"][0]
    assert float(counters["moe_tokens_skipped"]) == T
    assert float(counters["moe_assignments_held"]) == 0
    assert float(jnp.max(jnp.abs(z_out))) > 0
    scale = lm._ResidualScale()
    r = np.random.default_rng(0)
    p = {n: jnp.asarray(1 + 0.1 * r.normal(size=d), jnp.float32)
         for n in ("a_r", "a_y", "b_r", "b_y")}
    out = scale.apply({"params": p}, x, y)
    close(out - p["a_r"] * (x + p["b_r"]),
          jnp.broadcast_to(p["a_y"] * p["b_y"], out.shape), 1e-6)


# ------------------------------------------------- a share of the layer

def test_the_two_halves_add_up_to_the_uncut_layer():
    """Section 4's test: the two halves of the experts through the
    program's own block with a half's slice of the expert weights; what
    every chip computes alike (the attention, the router, the scaled
    stream and the sublayer's shift) is whole in each and counted once.
    Their sum is the uncut reference's layer."""
    _, model = tiny(**VARIANTS["one_whole_layer"])
    params, _ = inputs(5, (), columns=1, variant="one_whole_layer")
    bp = params["params"]["block_0"]
    T, d = 24, 32
    x = jax.random.normal(jax.random.PRNGKey(3), (T, 1, d))
    done = np.zeros((T, 1), bool)
    done[[7, 16]] = True
    seg_bt = jnp.asarray(np.cumsum(done, 0).T, jnp.int32)
    z0 = jnp.zeros((T, 1, 16))
    def uncut(x, z0, bp):
        want, want_z = reference.block(x, z0, bp, seg_bt[0], SPEC, CAST)
        # the stream after the attention sublayer, and what the expert
        # sublayer gives a token that no expert serves
        after = reference.merge(x, reference.attention(
            reference.rms(x, bp["norm1"]["scale"], 1e-5), bp["attn"],
            seg_bt[0], SPEC, CAST), bp["scale_attn"])
        return want, want_z, reference.merge(after, 0.0, bp["scale_mlp"])

    def halves(x, z0, bp):
        outs = []
        for first in (0, 2):
            half, _ = tiny(model, experts_held=[first, 2])
            share = dict(bp, moe=dict(bp["moe"], **{
                n: bp["moe"][n][first:first + 2]
                for n in ("w_gate", "w_up", "w_down")}))
            block = lm._Block(half.attention_kinds[0][1], "sparse",
                              half._sizes())
            outs.append(block.apply(
                {"params": share}, (x, z0), seg_bt, jnp.arange(T),
                mutable=["intermediates"])[0])
        return outs

    with jax.default_matmul_precision("highest"):
        want, want_z, alike = jax.jit(uncut)(x[:, 0], z0[:, 0], bp)
        total = alike  # counted once
        for out, z in jax.jit(halves)(x, z0, bp):
            total = total + out[:, 0] - alike
            close(z[:, 0], want_z, 5e-5)
    assert float(jnp.max(jnp.abs(want - alike))) > 0.05
    close(total, want, 5e-5)


# ---------------------------------------- what the other stacks compile to

def _scan_carries(jaxpr, length, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and eqn.params["length"] == length:
            found.append(eqn.params["num_carry"])
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                if hasattr(sub, "jaxpr"):
                    _scan_carries(sub.jaxpr, length, found)
                elif hasattr(sub, "eqns"):
                    _scan_carries(sub, length, found)
    return found


def test_a_stack_without_such_a_router_or_skeleton_is_what_it_was():
    """``initial_state`` ``()``, the blocks' carry ``x`` alone, the leaves
    the five other configurations build: a linear router, an untied head,
    no scales; with them, the carry is ``(x, z)``."""
    plain_kinds = {"full": {"window": None, "rope": {"theta": 10000.0}}}
    plain, _ = tiny(
        attention_kinds=plain_kinds, router={"scoring": "softmax"},
        layers=[{"attention": "full", "mlp": "sparse", "repeat": 3}],
        residual=None, tie_embeddings=False, top_k=2, remat_blocks=False)
    obs = jnp.zeros((8, B), jnp.int32)
    done = jnp.zeros((8, B), bool)
    shapes = seeded_cca.param_shapes(plain)["params"]
    assert plain.initial_state(3) == ()
    assert sorted(shapes) == ["baseline", "block_0", "embed", "final_norm",
                              "head"]
    assert sorted(shapes["block_0"]) == ["attn", "moe", "norm1", "norm2"]
    assert sorted(shapes["block_0"]["moe"]) == [
        "router", "w_down", "w_gate", "w_up"]
    assert sorted(shapes["block_0"]["attn"]) == ["k", "o", "q", "v"]
    params = jax.eval_shape(plain.init, jax.random.PRNGKey(0), obs, done, ())
    jaxpr = jax.make_jaxpr(lambda p: plain.apply(p, obs, done, ()))(params)
    assert _scan_carries(jaxpr.jaxpr, 3, []) == [1]
    deep, _ = tiny(remat_blocks=False)
    params = jax.eval_shape(deep.init, jax.random.PRNGKey(0), obs, done, ())
    jaxpr = jax.make_jaxpr(lambda p: deep.apply(p, obs, done, ()))(params)
    assert _scan_carries(jaxpr.jaxpr, 3, []) == [2]
    assert "head" not in params["params"]
    # every new field defaults to what the other configurations compile to
    r = lm.Router()
    assert (r.hidden_size, r.skip_choices, r.renormalize) == (None, 0, True)
    assert lm.Rope().partial_rotary_factor == 1.0


@pytest.mark.parametrize("what", ["residual", "streams_and_state",
                                  "rotary", "heads"])
def test_what_is_not_built_says_so(what):
    obs = jnp.zeros((4, 1), jnp.int32)
    done = jnp.zeros((4, 1), bool)
    if what == "residual":
        net, _ = tiny(residual="doubled")
        match = "absent, a Residual or 'scaled'"
    elif what == "streams_and_state":
        net, _ = tiny(num_pred_heads=2)
        match = "one stream and one prediction head"
    elif what == "rotary":
        net, _ = tiny(attention_kinds={
            "cca": dict(KINDS["cca"], rope=None)})
        match = "a rotary"
    else:
        net, _ = tiny(num_heads=3, num_kv_heads=3)
        match = "half of the key/value heads"
    with pytest.raises(ValueError, match=match):
        net.init(jax.random.PRNGKey(0), obs, done, ())


def test_the_tied_head_is_one_leaf_with_both_gradients():
    """No ``head`` leaf; the embedding's gradient is the lookup's plus
    the head's. A row whose token the sequence never holds gets nothing
    from the lookup and still has the head's; the whole equals the
    reference's, which writes the two uses apart
    (``test_loss_and_every_gradient_leaf_match_the_reference``)."""
    net, model = tiny()
    params, batch = inputs(7, BOUNDARIES["inside_the_unroll"])
    assert "head" not in params["params"]
    with jax.default_matmul_precision("highest"):
        (_, _), grads = _gradients(net)[0](params, batch)
    got = np.asarray(grads["params"]["embed"]["embedding"])
    absent = np.setdiff1d(np.arange(VOCAB), np.asarray(batch["obs"]))
    assert len(absent) > 0
    assert np.abs(got[absent]).max(axis=-1).min() > 0
    # the lookup alone: the same loss through an untied copy of the head
    E = params["params"]["embed"]["embedding"]
    lookup = jax.grad(lambda e: jnp.sum(jnp.tanh(e[batch["obs"]])))(E)
    assert float(jnp.max(jnp.abs(lookup[absent]))) == 0.0


# ------------------------------------------- the benchmark's configuration

def _config():
    with open(os.path.join(
            REPO, "benchmark", "configs", "zaya1_share8.json")) as f:
        return json.load(f)


def test_the_configuration_is_the_published_model_cut_as_it_says():
    config = _config()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "ZAYA1-8B")
    assert config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    for key, value in config["published"].items():
        assert row["config"][key] == value, key
    model = config["model"]["kwargs"]
    kind = model["attention_kinds"]["cca"]
    assert model["hidden_size"] == config["hidden_size"]
    assert model["head_dim"] == config["head_dim"]
    assert (model["num_heads"], model["num_kv_heads"]) == (
        config["num_attention_heads"], config["num_key_value_heads"])
    assert (kind["cca"]["time0"], kind["cca"]["time1"]) == (
        config["cca_time0"], config["cca_time1"])
    rope = config["rope_parameters"]["hybrid"]
    assert kind["rope"]["theta"] == rope["rope_theta"]
    assert kind["rope"]["partial_rotary_factor"] == rope[
        "partial_rotary_factor"] == config["partial_rotary_factor"]
    assert kind["window"] is config["sliding_window"] is None
    assert model["num_experts"] == config["published"]["num_experts"] == 16
    assert model["experts_held"] == [0, config["num_experts"]]
    assert model["top_k"] == config["num_experts_per_tok"] == 1
    assert model["moe_intermediate_size"] == config["moe_intermediate_size"]
    assert model["router"]["hidden_size"] == config["router_hidden_size"]
    assert model["router"]["skip_choices"] == 1
    assert model["router"]["renormalize"] is False
    assert model["tie_embeddings"] is config["tie_word_embeddings"] is True
    assert model["rms_norm_eps"] == config["rms_norm_eps"]
    assert model["vocab_size"] == config["vocab_size"] == config[
        "num_actions"] == config["published"]["vocab_size"] // 8
    layers = [l for l in model["layers"] for _ in range(l.get("repeat", 1))]
    assert len(layers) == config["num_hidden_layers"] == 5
    assert set(config["layer_types"]) == {"hybrid"}


def test_the_parameters_of_the_cut_are_counted():
    """601,748,064 held, 8.42 GB at the 14 B a parameter this repo trains
    at, from the program's shapes and from the description alone: the
    issue's table and 256 more, the first layer's ``gamma``, which a scan
    over stacked blocks holds and a zero state in leaves without
    effect."""
    config = _config()
    net = program.build_model(config)
    shapes = seeded_cca.param_shapes(net)
    count = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert count == counts_cca.parameters(config["model"]["kwargs"])
    assert count == 601_747_808 + 256
    block = shapes["params"]["block_0"]

    def size(tree):
        return sum(x.size for x in jax.tree_util.tree_leaves(tree)) // 5

    assert size(block["attn"]) == 5_575_682
    moe = dict(block["moe"])
    held = {n: moe.pop(n) for n in ("w_gate", "w_up", "w_down")}
    assert size(moe) == 661_009 and size(held) == 100_663_296
    assert size([block[n] for n in ("norm1", "norm2", "scale_attn",
                                    "scale_mlp")]) == 20_480
    assert shapes["params"]["embed"]["embedding"].shape == (32_784, 2048)
    assert net.initial_state(1) == ()
