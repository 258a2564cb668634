"""The decoder language model (``models/lm.py``) against its plain
reference (``benchmark/reference/mellum2_share8.py``: float32 ``jax.numpy``
from the equations, nothing of the program), on the CPU at tiny sizes with
seeded weights: logits, baseline, the first step's loss and every leaf of
its gradient. And the share tied to the model: what every share of a layer
gives adds up to the uncut layer."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.lib import reference_train, seeded_lm  # noqa: E402
from benchmark.reference import mellum2_share8 as ref  # noqa: E402
from benchmark.reference.mellum2_tiny import TINY  # noqa: E402
from moolib_tpu.learner import ImpalaConfig, impala_loss  # noqa: E402
from moolib_tpu.models.lm import (  # noqa: E402
    AttentionKind, Rope, _Attention, decoder_lm, learn_apply, rope_inv_freq,
    router_loads,
)
from moolib_tpu.models.transformer import segment_ids_from_done  # noqa: E402
from moolib_tpu.parallel import moe  # noqa: E402
from moolib_tpu.parallel.moe import linear_scores, moe_dropless  # noqa: E402

VOCAB, T, B = 48, 31, 2
LOSS = {"discounting": 0.99, "baseline_cost": 0.5, "entropy_cost": 0.0006,
        "reward_clip": 1.0}
ROPE_FULL = {"theta": 500000.0, "factor": 16.0,
             "original_max_position_embeddings": 32, "beta_fast": 32.0,
             "beta_slow": 1.0, "attention_factor": 1.2772588722239782}


def tiny_net(held=(2, 4), dtype=jnp.float32, **over):
    """Two blocks, one sliding (window 8 of T+1 = 32) and one full with
    YaRN; 4 query heads on 1 key/value head of 16; a router over 8, top-2,
    experts ``held`` of them."""
    kwargs = dict(
        vocab_size=VOCAB, hidden_size=32,
        layers=[{"attention": "sliding", "mlp": "sparse"},
                {"attention": "full", "mlp": "sparse"}],
        attention_kinds={
            "sliding": {"window": 8, "rope": {"theta": 500000.0}},
            "full": {"window": None, "rope": ROPE_FULL},
        },
        num_heads=4, num_kv_heads=1, head_dim=16, num_experts=8, top_k=2,
        moe_intermediate_size=24, experts_held=list(held),
        compute_dtype=dtype,
    )
    kwargs.update(over)
    return decoder_lm(**kwargs)


def tiny_inputs(net, seed, done_at=()):
    params = seeded_lm.make_params(seeded_lm.param_shapes(net), seed)
    config = {"num_actions": VOCAB,
              "observation": {"vocab": VOCAB, "zipf_s": 1.0}}
    batch = seeded_lm.make_learn_batch(seed, config, T, B, 0.0)
    done = np.zeros((T + 1, B), bool)
    for t in done_at:
        done[t, :] = True
    return params, dict(batch, done=jnp.asarray(done))


def all_to_one_expert(params):
    """Every token's first choice is expert 2 and its second expert 3: the
    stream's first element is made large and positive at every position,
    and the router reads only it."""
    p = jax.tree_util.tree_map(lambda x: x, params)["params"]
    p["embed"]["embedding"] = p["embed"]["embedding"].at[:, 0].set(6.0)
    for name in ("block_0", "block_1"):
        router = jnp.zeros_like(p[name]["moe"]["router"])
        router = router.at[0, 2].set(8.0).at[0, 3].set(4.0)
        p[name]["moe"]["router"] = router
        p[name]["norm2"]["scale"] = jnp.abs(p[name]["norm2"]["scale"])
    return {"params": p}


CASES = {
    # window 8 < T + 1 = 32; a boundary at 13 falls inside the windows of
    # 14..20; experts 2..5 of 8 held, so some tokens have none of theirs
    "strict_share": dict(held=(2, 4), done_at=(13, 27)),
    "all_held": dict(held=(0, 8), done_at=(5,)),
    "imbalance": dict(held=(2, 4), done_at=(), rewrite=all_to_one_expert),
}


def both_sides(case, dtype=jnp.float32, seed=7):
    spec = CASES[case]
    net = tiny_net(spec["held"], dtype)
    params, batch = tiny_inputs(net, seed, spec["done_at"])
    if "rewrite" in spec:
        params = spec["rewrite"](params)
    forward = ref.make_forward(dict(TINY, first_expert=spec["held"][0]))
    return net, params, batch, forward


def reference_loss_and_grad(forward, params, batch):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(reference_train.chunk_loss)(
            params, batch, forward, LOSS, float(T * B),
            reference_train.identity_cast,
        )


@pytest.mark.parametrize("case", sorted(CASES))
def test_float32_model_matches_the_reference(case):
    net, params, batch, forward = both_sides(case)
    (logits, baseline), _, counters = learn_apply(net)(
        params, batch["obs"], batch["done"], ()
    )
    r_logits, r_baseline, _ = forward(
        params, batch["obs"], batch["done"], (),
        reference_train.identity_cast,
    )
    # float32 both sides: what is left is the order of the sums
    np.testing.assert_allclose(logits, r_logits, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(baseline, r_baseline, rtol=2e-4, atol=2e-4)

    (loss, _), grads = jax.value_and_grad(impala_loss, has_aux=True)(
        params, learn_apply(net), batch, ImpalaConfig(**LOSS)
    )
    r_loss, r_grads = reference_loss_and_grad(forward, params, batch)
    assert float(loss) == pytest.approx(float(r_loss), rel=1e-4)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), r in zip(flat, jax.tree_util.tree_leaves(r_grads)):
        scale = max(float(jnp.max(jnp.abs(r))), 1e-6)
        assert float(jnp.max(jnp.abs(g - r))) <= 2e-3 * scale, (
            jax.tree_util.keystr(path)
        )

    assert float(counters["moe_overflow"]) == 0.0  # nothing dropped
    total = 2 * (T + 1) * B * 2  # layers x tokens x top-2
    assert float(counters["moe_assignments_total"]) == total
    if case == "all_held":
        assert float(counters["moe_assignments_held"]) == total
        assert float(counters["moe_tokens_unserved"]) == 0.0
    if case == "strict_share":
        assert 0 < float(counters["moe_assignments_held"]) < total
        assert float(counters["moe_tokens_unserved"]) > 0  # y = 0 there
    if case == "imbalance":
        # every token of both layers went to experts 2 and 3, both held:
        # the fullest expert holds every token, and none fell off
        assert float(counters["moe_assignments_held"]) == total
        assert float(counters["moe_load_max"]) == (T + 1) * B


@pytest.mark.parametrize("case", ["strict_share", "all_held"])
def test_bfloat16_model_stays_near_the_reference(case):
    """bfloat16 compute over float32 parameters against the float32
    reference. Tolerance: bfloat16 keeps 8 bits, a relative 4e-3 a
    rounding; a logit is a sum of 32 products behind two blocks of such
    sums, and on near-tied experts the two sides may pick differently,
    which moves a token's expert output by a gate's worth. 0.15 of the
    largest logit holds all of that with room, and is far below what a
    dropped window or expert does (a logit's own size)."""
    net, params, batch, forward = both_sides(case, jnp.bfloat16)
    (logits, baseline), _ = net.apply(
        params, batch["obs"], batch["done"], ()
    )
    r_logits, r_baseline, _ = forward(
        params, batch["obs"], batch["done"], (),
        reference_train.identity_cast,
    )
    assert logits.dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(r_logits)))
    assert float(jnp.max(jnp.abs(logits - r_logits))) < 0.15 * scale
    assert float(jnp.max(jnp.abs(baseline - r_baseline))) < 0.15 * max(
        float(jnp.max(jnp.abs(r_baseline))), 1.0
    )
    (loss, _), grads = jax.value_and_grad(impala_loss, has_aux=True)(
        params, learn_apply(net), batch, ImpalaConfig(**LOSS)
    )
    r_loss, r_grads = reference_loss_and_grad(forward, params, batch)
    assert float(loss) == pytest.approx(float(r_loss), rel=0.05)
    # Per leaf, on the scale lib/compare.py uses (the leaf's norm or the
    # median leaf's, whichever is larger): 0.015 where both sides pick the
    # same experts, 0.11-0.15 on the router's leaf where one of the 64
    # tokens' near-tied choices flips (seeds 7 to 9 of these two cases);
    # 0.3 holds a flip or two and is a third of what a dropped part does.
    norms = [float(jnp.linalg.norm(r))
             for r in jax.tree_util.tree_leaves(r_grads)]
    for g, r, norm in zip(jax.tree_util.tree_leaves(grads),
                          jax.tree_util.tree_leaves(r_grads), norms):
        assert float(jnp.linalg.norm(g - r)) <= 0.3 * max(
            norm, float(np.median(norms))
        )


@pytest.mark.parametrize("fault", ["window", "segments", "expert", "yarn"])
def test_the_comparison_sees_a_missing_part(fault):
    """What the tolerances above are tight enough for: the reference with a
    part of the mathematics taken out no longer matches the model."""
    net, params, batch, _ = both_sides("strict_share")
    spec = dict(TINY, first_expert=2)
    if fault == "window":
        spec["window"] = 10 ** 6
    elif fault == "yarn":
        spec["yarn"] = dict(spec["yarn"], factor=1.0, attention_factor=1.0)
    elif fault == "expert":
        params = jax.tree_util.tree_map(lambda x: x, params)
        moe = params["params"]["block_1"]["moe"]
        moe["w_down"] = moe["w_down"].at[1].set(0.0)
    done = batch["done"]
    if fault == "segments":
        done = jnp.zeros_like(done)
    (logits, _), _ = net.apply(params, batch["obs"], batch["done"], ())
    if fault == "expert":  # the reference still has the expert
        params = tiny_inputs(net, 7, (13, 27))[0]
    r_logits, _, _ = ref.make_forward(spec)(
        params, batch["obs"], done, (), reference_train.identity_cast
    )
    assert float(jnp.max(jnp.abs(logits - r_logits))) > 1e-2


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """8 experts over 4 shares of 2, 4 query heads (2 key/value heads) over
    2 shares of 2 (and 1): the partial sums every share gives for one
    layer, added, are the reference's uncut layer."""
    d, D, H, Hkv, E, f, top_k, Tn = 32, 16, 4, 2, 8, 24, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 9)

    def normal(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)

    attn = {"q": {"kernel": normal(ks[0], (d, H * D), d)},
            "k": {"kernel": normal(ks[1], (d, Hkv * D), d)},
            "v": {"kernel": normal(ks[2], (d, Hkv * D), d)},
            "o": {"kernel": normal(ks[3], (H * D, d), H * D)}}
    moe = {"router": normal(ks[4], (d, E), d),
           "w_gate": normal(ks[5], (E, d, f), d),
           "w_up": normal(ks[6], (E, d, f), d),
           "w_down": normal(ks[7], (E, f, d), f)}
    z = jax.random.normal(ks[8], (Tn, d), jnp.float32)
    done = np.zeros((Tn, 1), bool)
    done[11] = True
    seg = jnp.cumsum(jnp.asarray(done[:, 0]).astype(jnp.int32))
    spec = dict(TINY, first_expert=0)
    cast = reference_train.identity_cast

    # attention: share s holds query heads 2s, 2s+1 and key/value head s
    kind = AttentionKind(8, Rope(theta=500000.0))
    module = _Attention(kind, 2, 1, D, "dense", 16, jnp.float32)
    parts = []
    for s in range(2):
        cols = slice(2 * s * D, (2 * s + 2) * D)
        kv = slice(s * D, (s + 1) * D)
        share = {"params": {
            "q": {"kernel": attn["q"]["kernel"][:, cols]},
            "k": {"kernel": attn["k"]["kernel"][:, kv]},
            "v": {"kernel": attn["v"]["kernel"][:, kv]},
            "o": {"kernel": attn["o"]["kernel"][cols, :]},
        }}
        parts.append(module.apply(
            share, z[:, None, :], segment_ids_from_done(jnp.asarray(done)),
            jnp.arange(Tn),
        )[:, 0])
    whole = ref.attention(z, attn, seg, spec, True, cast)
    np.testing.assert_allclose(sum(parts), whole, rtol=2e-4, atol=2e-4)

    # experts: share s holds experts 2s, 2s+1 of the router's 8
    parts = []
    for s in range(4):
        rows = slice(2 * s, 2 * s + 2)
        share = {"w_gate": moe["w_gate"][rows], "w_up": moe["w_up"][rows],
                 "w_down": moe["w_down"][rows]}
        y, _ = moe_dropless(share, z, linear_scores(z, moe["router"]),
                            top_k=top_k, held=(2 * s, 2))
        parts.append(y)
    whole = ref.experts(z, moe, spec, cast)
    np.testing.assert_allclose(sum(parts), whole, rtol=2e-4, atol=2e-4)


def test_yarn_inv_freq_and_attention_factor_by_hand():
    """Head 128, theta 500000, factor 16 over 8192, beta 32 / 1:
    dim(n) = 128 ln(8192 / (2 pi n)) / (2 ln 500000), so dim(32) = 18.08
    and dim(1) = 34.98: low 18, high 35. Below index 18 a frequency keeps
    its value, from 35 on it is divided by 16, between them the blend."""
    rope = Rope(theta=500000.0, factor=16.0,
                original_max_position_embeddings=8192, beta_fast=32.0,
                beta_slow=1.0, attention_factor=1.2772588722239782)
    got = rope_inv_freq(rope, 128)
    plain = rope_inv_freq(Rope(theta=500000.0), 128)
    assert got.shape == (64,)
    assert plain[1] == pytest.approx(500000.0 ** (-2 / 128))
    assert 128 * np.log(8192 / (2 * np.pi * 32)) / (
        2 * np.log(500000.0)) == pytest.approx(18.08, abs=0.01)
    assert 128 * np.log(8192 / (2 * np.pi * 1)) / (
        2 * np.log(500000.0)) == pytest.approx(34.98, abs=0.01)
    np.testing.assert_allclose(got[:19], plain[:19])
    np.testing.assert_allclose(got[35:], plain[35:] / 16)
    # index 20: ramp (20 - 18) / (35 - 18) = 2 / 17
    assert got[20] == pytest.approx(
        plain[20] / 16 * (2 / 17) + plain[20] * (15 / 17)
    )
    assert got[20] == pytest.approx(0.014734, rel=1e-3)
    # attention_factor is 0.1 ln(factor) + 1
    assert rope.attention_factor == pytest.approx(0.1 * np.log(16) + 1)
    # and the reference computes the same frequencies by its own code
    np.testing.assert_allclose(
        ref.inv_freq(ref.PUBLISHED, yarn=True), got, rtol=1e-6
    )


def test_the_model_keeps_the_agent_convention():
    net = tiny_net()
    params, batch = tiny_inputs(net, 11, (9,))
    (logits, baseline), state = net.apply(
        params, batch["obs"], batch["done"], net.initial_state(B)
    )
    assert logits.shape == (T + 1, B, VOCAB) and baseline.shape == (T + 1, B)
    assert state == () and net.initial_state(B) == ()


def test_router_loads_counts_every_layers_routing_and_the_counters_agree():
    net = tiny_net(held=(2, 4))
    params, batch = tiny_inputs(net, 13, (9,))
    loads = np.asarray(router_loads(net)(
        params, batch["obs"], batch["done"]
    ))
    assert loads.shape == (2, 8) and loads.dtype == np.int32
    assert list(loads.sum(axis=1)) == [(T + 1) * B * 2] * 2  # top-2
    _, _, counters = learn_apply(net)(params, batch["obs"], batch["done"], ())
    assert float(counters["moe_assignments_held"]) == loads[:, 2:6].sum()
    assert "moe_router_load" not in counters  # an array, not a counter


def test_a_buffer_too_small_spills_and_drops_nothing():
    bounded = tiny_net(held=(2, 4), moe_buffer_rows=8)
    params, batch = tiny_inputs(bounded, 5)
    (logits, _), _, counters = learn_apply(bounded)(
        params, batch["obs"], batch["done"], ()
    )
    (whole, _), _ = tiny_net(held=(2, 4)).apply(
        params, batch["obs"], batch["done"], ()
    )
    assert float(counters["moe_assignments_held"]) > 16  # 8 rows a layer
    assert float(counters["moe_spills"]) == 2.0  # both layers spilled
    assert float(counters["moe_overflow"]) == 0.0
    np.testing.assert_allclose(logits, whole, rtol=1e-5, atol=1e-5)


def _row_moves(jaxpr, in_while=False):
    """``(rows, in a while)`` of every gather and scatter of two-dimensional
    rows in ``jaxpr`` and the programs its equations hold."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "gather" and eqn.outvars[0].aval.ndim == 2:
            found.append((eqn.outvars[0].aval.shape[0], in_while))
        if name.startswith("scatter") and eqn.invars[2].aval.ndim == 2:
            found.append((eqn.invars[2].aval.shape[0], in_while))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _row_moves(sub, in_while or name == "while")
    return found


def test_no_pass_over_a_whole_expert_buffer_stands_outside_a_loop(
        monkeypatch):
    """Counts, not timings: in the gradient step of the two-layer stack
    with a stated buffer of six tiles, every gather and scatter-add of
    rows moves one tile inside a ``while`` whose trip count is the rows in
    use; none moves the buffer (or the worst case's) at once."""
    tile, buffer_rows, worst = 16, 96, (T + 1) * B * 2
    monkeypatch.setattr(moe, "_WALK_TILE", tile)
    net = tiny_net(held=(2, 4), moe_buffer_rows=buffer_rows)
    params, batch = tiny_inputs(net, 5)
    step = jax.make_jaxpr(jax.grad(
        lambda p: impala_loss(p, learn_apply(net), batch,
                              ImpalaConfig(**LOSS))[0]
    ))(params)
    moves = _row_moves(step.jaxpr)
    assert not [m for m in moves if m[0] in (buffer_rows, worst)]
    # a layer and branch: gather and combine forward, the gather rebuilt,
    # and a transpose each; two layers, two branches
    assert moves.count((tile, True)) == 2 * 2 * 5
    assert (tile, False) not in moves


def test_the_experiment_reaches_the_model_by_its_model_switch():
    from moolib_tpu.examples.vtrace.experiment import (VtraceConfig,
                                                       _make_model)
    from moolib_tpu.models import DecoderLM

    path = os.path.join(
        REPO, "benchmark", "tests", "rehearsal_lm", "benchmark", "configs",
        "tiny_lm.json",
    )
    net = _make_model(VtraceConfig(
        model="decoder_lm", lm_config=path, compute_dtype="float32"
    ))
    assert isinstance(net, DecoderLM)
    assert net.layers == (("sliding", "sparse"), ("full", "sparse"))
    assert net.experts_held == (2, 4) and net.compute_dtype == jnp.float32
    with pytest.raises(ValueError, match="lm_config"):
        _make_model(VtraceConfig(model="decoder_lm"))
