"""The decoder's latent attention, dense and shared-expert MLPs,
bias-corrected sigmoid routing and multi-token-prediction module
(``models/lm.py``, ``parallel/moe.py``, ``learner.py``) against their plain
reference (``benchmark/reference/glm47_flash_share8.py``: float32
``jax.numpy`` from the equations, nothing of the program), on the CPU at
tiny sizes with seeded weights: piece by piece, then the whole learner
step. And what must not have moved: the other decoder configuration's
parameter tree and step program."""

import hashlib
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

from benchmark.lib import program, reference_train, seeded_latent  # noqa: E402
from benchmark.lib import seeded_lm  # noqa: E402
from benchmark.reference import glm47_flash_share8 as ref  # noqa: E402
from benchmark.reference.glm47_tiny import TINY  # noqa: E402
from moolib_tpu.learner import (ImpalaConfig, impala_loss,  # noqa: E402
                                make_impala_train_step, make_train_state)
from moolib_tpu.models.lm import (DecoderLM, _Block, _LatentAttention,  # noqa: E402
                                  _Mtp, decoder_lm, learn_apply, router_loads)
from moolib_tpu.models.transformer import segment_ids_from_done  # noqa: E402
from moolib_tpu.parallel.moe import linear_scores, moe_dropless  # noqa: E402

VOCAB, T, B = 48, 31, 2
LOSS = {"discounting": 0.99, "baseline_cost": 0.5, "entropy_cost": 0.0006,
        "reward_clip": 1.0, "mtp_cost": 0.1}
CAST = reference_train.identity_cast
MODEL = dict(
    vocab_size=VOCAB, hidden_size=32,
    layers=[{"attention": "latent", "mlp": "dense"},
            {"attention": "latent", "mlp": "sparse", "repeat": 2}],
    attention_kinds={"latent": {
        "window": None, "rope": {"theta": 1000000.0},
        "latent": {"q_lora_rank": 12, "kv_lora_rank": 8,
                   "qk_nope_head_dim": 12, "qk_rope_head_dim": 4,
                   "v_head_dim": 16},
    }},
    num_heads=3, num_kv_heads=3, head_dim=16, num_experts=8,
    experts_held=[2, 4], top_k=2, moe_intermediate_size=24,
    router={"scoring": "sigmoid", "selection_bias": True, "gate_scale": 1.8},
    shared_expert_size=24, intermediate_size=40,
    mtp={"attention": "latent", "mlp": "sparse"}, mtp_loss_rows=16,
    remat_blocks=True, rms_norm_eps=1e-5,
)


def tiny(**over):
    model = dict(MODEL, **over)
    return decoder_lm(**model), model


def inputs(net, model, seed, done_at=(13, 27), bias_scale=0.05):
    params = seeded_latent.make_params(
        seeded_latent.param_shapes(net), seed, model, bias_scale
    )
    config = {"num_actions": VOCAB,
              "observation": {"vocab": VOCAB, "zipf_s": 1.0}}
    batch = seeded_lm.make_learn_batch(seed, config, T, B, 0.0)
    done = np.zeros((T + 1, B), bool)
    for t in done_at:
        done[t, :] = True
    return params, dict(batch, done=jnp.asarray(done))


def close(a, b, tol=2e-4):
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol)


@pytest.fixture(scope="module")
def whole():
    net, model = tiny()
    params, batch = inputs(net, model, 7)
    return net, model, params, batch


def stream(seed=3, width=32):
    """A stand-in for the residual stream, [T+1, d], and its segments."""
    z = jax.random.normal(jax.random.PRNGKey(seed), (T + 1, width))
    done = np.zeros((T + 1, 1), bool)
    done[11] = True
    return z, jnp.asarray(done)


def sizes(net):
    return net._sizes(), dict(net.attention_kinds)["latent"]


def test_one_latent_attention_layer_matches_the_reference(whole):
    net, _, params, _ = whole
    net_sizes, kind = sizes(net)
    z, done = stream()
    p = params["params"]["block_0"]["attn"]
    module = _LatentAttention(kind, 3, "dense", 16, 1e-5, jnp.float32)
    got = module.apply(
        {"params": p}, z[:, None, :], segment_ids_from_done(done),
        jnp.arange(T + 1),
    )[:, 0]
    seg = jnp.cumsum(done[:, 0].astype(jnp.int32))
    close(got, ref.attention(z, p, seg, TINY, CAST))
    # one rotary key a position: a second head's rope part is the first's
    assert p["kv_a"]["kernel"].shape == (32, 8 + 4)
    assert p["kv_b"]["kernel"].shape == (8, 3 * (12 + 16))
    # the boundary cuts: without it the outputs after it change
    open_seg = jnp.zeros_like(seg)
    assert float(jnp.max(jnp.abs(
        ref.attention(z, p, open_seg, TINY, CAST)[12:] - got[12:]
    ))) > 1e-3


@pytest.mark.parametrize("name,mlp", [("block_0", "dense"),
                                      ("mtp", "sparse")])
def test_one_block_matches_the_reference(whole, name, mlp):
    """The dense block, and a sparse block with its shared expert and a
    seeded correction bias (the prediction module's: not stacked)."""
    net, _, params, _ = whole
    net_sizes, kind = sizes(net)
    p = params["params"][name]
    p = p["block"] if name == "mtp" else p
    assert ("mlp" in p) == (mlp == "dense")
    z, done = stream(5)
    got, inter = _Block(kind, mlp, net_sizes).apply(
        {"params": p}, z[:, None, :], segment_ids_from_done(done),
        jnp.arange(T + 1), mutable=["intermediates"],
    )
    seg = jnp.cumsum(done[:, 0].astype(jnp.int32))
    close(got[:, 0], ref.block(z, p, seg, TINY, CAST))
    if mlp == "sparse":
        counters = inter["intermediates"]["moe"]["moe_counters"][0]
        assert 0 < float(counters["moe_assignments_held"]) < 2 * (T + 1)
        # the reference without the shared expert no longer matches
        bare = dict(p, moe=dict(p["moe"], shared=jax.tree_util.tree_map(
            jnp.zeros_like, p["moe"]["shared"])))
        assert float(jnp.max(jnp.abs(
            ref.block(z, bare, seg, TINY, CAST) - got[:, 0]
        ))) > 1e-2


def test_the_prediction_module_and_its_masked_loss(whole):
    net, _, params, batch = whole
    net_sizes, kind = sizes(net)
    p = params["params"]
    h = jax.random.normal(jax.random.PRNGKey(9), (T + 1, B, 32))
    obs = batch["obs"]
    e = p["embed"]["embedding"][obs]
    seg_bt = segment_ids_from_done(batch["done"])
    loss, count = _Mtp(kind, "sparse", net_sizes, True, 16).apply(
        {"params": p["mtp"]}, h, e, obs, seg_bt, jnp.arange(T + 1),
        p["head"]["kernel"],
    )
    total = positions = 0.0
    for c in range(B):
        seg = seg_bt[c]
        u = ref.mtp_hidden(p, e[:, c], h[:, c], seg, TINY, CAST)
        logp = jax.nn.log_softmax(u @ p["head"]["kernel"], axis=-1)
        valid = np.asarray(ref.mtp_valid(seg))
        # by hand: boundaries at 13 and 27 of 32 positions; t counts when
        # t + 2 lies in t's episode
        by_hand = [t for t in range(T + 1) if t + 2 <= T
                   and not (t < 13 <= t + 2) and not (t < 27 <= t + 2)]
        assert list(np.flatnonzero(valid)) == by_hand
        for t in by_hand:
            total -= float(logp[t, int(obs[t + 2, c])])
        positions += len(by_hand)
    assert float(count) == positions == 2 * 26
    assert float(loss) == pytest.approx(total / positions, rel=1e-4)


def reference_loss_and_grad(params, batch):
    from benchmark.reference.glm47_tiny import loss_fn

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, LOSS, CAST
        )


def test_the_whole_learner_step_matches_the_reference(whole):
    """Loss with the module's term in it, every leaf of the gradient, and
    the update: one step of the jitted train step against clip and RMSProp
    written out."""
    net, _, params, batch = whole
    (logits, baseline), _, aux = learn_apply(net)(
        params, batch["obs"], batch["done"], ()
    )
    from benchmark.reference.glm47_tiny import forward

    r_logits, r_baseline, _ = forward(
        params, batch["obs"], batch["done"], (), CAST
    )
    close(logits, r_logits)
    close(baseline, r_baseline)

    config = ImpalaConfig(**LOSS)
    (loss, metrics), grads = jax.value_and_grad(impala_loss, has_aux=True)(
        params, learn_apply(net), batch, config
    )
    (r_loss, r_parts), r_grads = reference_loss_and_grad(params, batch)
    assert float(loss) == pytest.approx(float(r_loss), rel=1e-4)
    assert float(metrics["mtp_loss"]) == pytest.approx(
        float(r_parts["mtp_loss"]), rel=1e-4
    )
    assert float(metrics["mtp_positions"]) == float(
        r_parts["mtp_positions"]
    ) == 52.0
    # the term is in the total with its weight
    assert float(metrics["total_loss"]) == pytest.approx(
        float(metrics["pg_loss"]) + 0.5 * float(metrics["baseline_loss"])
        - 0.0006 * float(metrics["entropy"])
        + 0.1 * float(metrics["mtp_loss"]), rel=1e-5,
    )
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), r in zip(flat, jax.tree_util.tree_leaves(r_grads)):
        name = jax.tree_util.keystr(path)
        if name.endswith("e_score_correction_bias']"):
            assert not np.any(np.asarray(g)) and not np.any(np.asarray(r))
            continue
        scale = max(float(jnp.max(jnp.abs(r))), 1e-6)
        assert float(jnp.max(jnp.abs(g - r))) <= 2e-3 * scale, name

    optimizer = program.build_optimizer({"optimizer": {
        "grad_clip": 40.0, "learning_rate": 0.0006, "decay": 0.99,
        "eps": 0.01}})
    step = make_impala_train_step(
        learn_apply(net), optimizer, config, mesh=None, donate=False
    )
    state, _ = step(make_train_state(params, optimizer), batch)
    norm = np.sqrt(sum(float(jnp.sum(x * x))
                       for x in jax.tree_util.tree_leaves(r_grads)))
    clip = min(1.0, 40.0 / norm)
    for new, old, g in zip(jax.tree_util.tree_leaves(state.params),
                           jax.tree_util.tree_leaves(params),
                           jax.tree_util.tree_leaves(r_grads)):
        g = np.asarray(g, np.float64) * clip
        want = np.asarray(old, np.float64) - 0.0006 * g / np.sqrt(
            0.01 * g * g + 0.01
        )
        np.testing.assert_allclose(new, want, rtol=1e-4, atol=2e-6)
    bias = state.params["params"]["block_1"]["moe"]["e_score_correction_bias"]
    np.testing.assert_array_equal(  # the optimizer leaves it
        bias, params["params"]["block_1"]["moe"]["e_score_correction_bias"]
    )


def moe_parts(seed=11, E=8, d=32, f=24):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)

    def normal(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)

    moe = {"router": normal(ks[0], (d, E), d),
           "e_score_correction_bias": 0.05 * jax.random.normal(ks[1], (E,)),
           "w_gate": normal(ks[2], (E, d, f), d),
           "w_up": normal(ks[3], (E, d, f), d),
           "w_down": normal(ks[4], (E, f, d), f),
           "shared": {"gate": {"kernel": normal(ks[5], (d, f), d)},
                      "up": {"kernel": normal(ks[6], (d, f), d)},
                      "down": {"kernel": normal(ks[7], (f, d), f)}}}
    return moe, jax.random.normal(ks[8], (64, d), jnp.float32)


def dropless(moe, z, held, bias=True, **over):
    first, count = held
    rows = slice(first, first + count)
    share = {"w_gate": moe["w_gate"][rows], "w_up": moe["w_up"][rows],
             "w_down": moe["w_down"][rows]}
    kw = dict(top_k=2, held=held, gate_scale=1.8,
              select_bias=moe["e_score_correction_bias"] if bias else None)
    kw.update(over)
    return moe_dropless(
        share, z, linear_scores(z, moe["router"], "sigmoid"), **kw
    )


def test_the_bias_moves_the_selection_and_not_the_gates():
    moe, z = moe_parts()
    E = 8
    y, aux = dropless(moe, z, (0, E))
    y_plain, aux_plain = dropless(moe, z, (0, E), bias=False)
    # at the seeded scale it moves at least 1% of the assignments
    moved = np.abs(np.asarray(aux["moe_router_load"])
                   - np.asarray(aux_plain["moe_router_load"])).sum() / 2
    assert moved >= 0.01 * 64 * 2, moved
    # the gates are the unbiased scores of the chosen, renormalised, x 1.8
    scores = jax.nn.sigmoid(z @ moe["router"])
    _, chosen = jax.lax.top_k(scores + moe["e_score_correction_bias"], 2)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = 1.8 * picked / picked.sum(-1, keepdims=True)
    want = jnp.zeros_like(z)
    for e in range(E):
        g = jnp.where(chosen == e, gates, 0.0).sum(-1)
        hidden = jax.nn.silu(z @ moe["w_gate"][e]) * (z @ moe["w_up"][e])
        want = want + g[:, None] * (hidden @ moe["w_down"][e])
    close(y, want)
    # a bias large on one expert seats it in every token's choice, and the
    # output is still a convex mix x 1.8: the bias is in no gate
    big = moe["e_score_correction_bias"].at[3].set(10.0)
    _, aux_big = dropless(dict(moe, e_score_correction_bias=big), z, (0, E))
    assert int(aux_big["moe_router_load"][3]) == 64


def test_the_bias_takes_exactly_no_gradient():
    moe, z = moe_parts()

    def loss(bias, router):
        y, _ = dropless(
            dict(moe, e_score_correction_bias=bias, router=router), z, (2, 4)
        )
        return jnp.sum(y * y)

    g_bias, g_router = jax.grad(loss, argnums=(0, 1))(
        moe["e_score_correction_bias"], moe["router"]
    )
    assert g_bias.shape == (8,) and not np.any(np.asarray(g_bias))
    assert float(jnp.max(jnp.abs(g_router))) > 0  # the gates do learn


def test_the_default_router_is_untouched_and_an_unknown_rule_refused():
    moe, z = moe_parts()
    share = {k: moe[k] for k in ("w_gate", "w_up", "w_down")}
    y, _ = moe_dropless(share, z, linear_scores(z, moe["router"]), top_k=2)
    probs = jax.nn.softmax(z @ moe["router"], axis=-1)
    top_p, top_i = jax.lax.top_k(probs, 2)
    gates = top_p / top_p.sum(-1, keepdims=True)
    want = jnp.zeros_like(z)
    for e in range(8):
        g = jnp.where(top_i == e, gates, 0.0).sum(-1)
        hidden = jax.nn.silu(z @ moe["w_gate"][e]) * (z @ moe["w_up"][e])
        want = want + g[:, None] * (hidden @ moe["w_down"][e])
    close(y, want)
    with pytest.raises(ValueError, match="scoring"):
        linear_scores(z, moe["router"], "tanh")


@pytest.mark.parametrize("name,f,scale", [
    ("glm47_flash_share8", 24, 1.8), ("xing4_share8", 16, 2.0),
])
def test_the_eight_shares_and_the_shared_expert_add_up_to_the_uncut_layer(
        name, f, scale):
    """64 experts over 8 shares of 8, top-4, the configuration's gate
    scale: the routed parts every share gives, added, plus the shared
    expert counted once, are the reference's uncut layer (its spec holding
    all 64)."""
    E, d = 64, 32
    moe, z = moe_parts(seed=13, E=E, d=d, f=f)
    spec = dict(TINY, top_k=4, first_expert=0, routed_scaling_factor=scale)
    whole = ref.experts(z, moe, spec, CAST)
    parts = [dropless(moe, z, (8 * s, 8), top_k=4, gate_scale=scale)[0]
             for s in range(8)]
    shared = ref.gated(z, moe["shared"], CAST)
    close(sum(parts) + shared, whole)
    # a share alone is not the layer, nor are seven of them, nor is the
    # layer under the other configuration's scale
    assert float(jnp.max(jnp.abs(sum(parts[:7]) + shared - whole))) > 1e-3
    other = dict(spec, routed_scaling_factor=3.8 - scale)
    assert float(jnp.max(jnp.abs(
        ref.experts(z, moe, other, CAST) - whole))) > 1e-3


def test_the_comparison_sees_a_missing_part(whole):
    net, _, params, batch = whole
    (logits, _), _ = net.apply(params, batch["obs"], batch["done"], ())
    for fault in ("bias", "scale", "rope"):
        spec, p = dict(TINY), params
        if fault == "scale":
            spec["routed_scaling_factor"] = 1.0
        elif fault == "rope":
            spec["theta"] = 10000.0
        else:
            p = jax.tree_util.tree_map_with_path(
                lambda path, x: jnp.zeros_like(x)
                if jax.tree_util.keystr(path).endswith("bias']")
                and "moe" in jax.tree_util.keystr(path) else x, params)
        r_logits, _, _ = ref.make_forward(spec)(
            p, batch["obs"], batch["done"], (), CAST
        )
        assert float(jnp.max(jnp.abs(logits - r_logits))) > 1e-2, fault


def test_counters_and_router_loads_read_a_stack_and_the_module(whole):
    net, model, params, batch = whole
    loads = np.asarray(router_loads(net)(params, batch["obs"], batch["done"]))
    # two stacked sparse blocks and the module's; the dense block has none
    assert loads.shape == (3, 8) and loads.dtype == np.int32
    assert list(loads.sum(axis=1)) == [(T + 1) * B * 2] * 3
    _, _, aux = learn_apply(net)(params, batch["obs"], batch["done"], ())
    assert float(aux["moe_assignments_held"]) == loads[:, 2:6].sum()
    assert float(aux["moe_assignments_total"]) == 3 * (T + 1) * B * 2
    assert float(aux["moe_overflow"]) == 0.0
    layers = seeded_latent.expert_layers(params)
    assert layers == [(("block_1", "moe"), 0), (("block_1", "moe"), 1),
                      (("mtp", "block", "moe"), None)]
    # the labelling moves a router's columns and its bias's entries alike
    perm = np.roll(np.arange(8), 3)
    moved = seeded_latent.permute_routers(params, [None, perm, perm])
    old, new = (t["params"]["block_1"]["moe"] for t in (params, moved))
    np.testing.assert_array_equal(new["router"][0], old["router"][0])
    np.testing.assert_array_equal(new["router"][1], old["router"][1][:, perm])
    np.testing.assert_array_equal(
        new["e_score_correction_bias"][1],
        old["e_score_correction_bias"][1][perm],
    )
    relabelled = np.asarray(
        router_loads(net)(moved, batch["obs"], batch["done"])
    )
    np.testing.assert_array_equal(relabelled[0], loads[0])
    np.testing.assert_array_equal(relabelled[1], loads[1][perm])


def test_a_stack_is_its_blocks_one_after_the_other(whole):
    """The scan over stacked parameters against the same blocks written
    out, and rebuilding in the backward pass against keeping."""
    net, model, params, batch = whole
    flat = dict(model, layers=[
        {"attention": "latent", "mlp": "dense"},
        {"attention": "latent", "mlp": "sparse"},
        {"attention": "latent", "mlp": "sparse"}], remat_blocks=False)
    p = dict(params["params"])
    stacked = p.pop("block_1")
    for j in range(2):
        p[f"block_{j + 1}"] = jax.tree_util.tree_map(lambda x: x[j], stacked)
    (logits, baseline), _ = net.apply(params, batch["obs"], batch["done"], ())
    (f_logits, f_baseline), _ = decoder_lm(**flat).apply(
        {"params": p}, batch["obs"], batch["done"], ()
    )
    close(logits, f_logits, 1e-5)
    close(baseline, f_baseline, 1e-5)


def test_a_rebuilt_block_keeps_its_attention_core(monkeypatch):
    """``remat_blocks`` with the flash kernels (in Pallas' interpreter):
    every block program traced counts under ``attention_cores_kept_total``
    and none does without the rebuild; loss and every gradient leaf are,
    bit for bit, those of the same rebuild with nothing named kept (the
    program before the policy), and those of the model that rebuilds
    nothing to the last bits XLA's own regrouping leaves on the CPU."""
    import functools

    from flax import linen as nn

    from moolib_tpu.ops import attention as attn_ops
    from moolib_tpu.telemetry import global_telemetry

    monkeypatch.setattr(attn_ops, "flash_attention", functools.partial(
        attn_ops.flash_attention, interpret=True))
    registry = global_telemetry().registry

    def counts():
        return (
            registry.value("attention_cores_kept_total") or 0,
            registry.value("attention_calls_traced_total", backend="flash")
            or 0,
        )

    def loss_and_grads(remat):
        net, model = tiny(remat_blocks=remat, attention_backend="flash",
                          attention_block=16)
        params, batch = inputs(net, model, 7)
        kept, calls = counts()
        (loss, _), grads = jax.value_and_grad(impala_loss, has_aux=True)(
            params, learn_apply(net), batch, ImpalaConfig(**LOSS)
        )
        after = counts()
        return loss, grads, after[0] - kept, after[1] - calls

    loss, grads, kept, calls = loss_and_grads(True)
    # the dense block, the stack's one block (flax traces a scan's body
    # twice) and the module's: every flash call of the trace, and no fewer
    # than the model has block programs
    assert kept == calls >= 3
    p_loss, p_grads, p_kept, p_calls = loss_and_grads(False)
    assert p_kept == 0 and p_calls == calls

    remat = nn.remat
    monkeypatch.setattr(  # the same rebuild, no policy: nothing kept
        nn, "remat", lambda cls, prevent_cse, policy: remat(
            cls, prevent_cse=prevent_cse))
    r_loss, r_grads, _, _ = loss_and_grads(True)
    assert float(loss) == float(r_loss)
    # the model that rebuilds nothing hands the loss logits and baselines
    # 1e-6 from these in their last bits, as it hands the gradients below
    assert float(loss) == pytest.approx(float(p_loss), rel=2e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    assert len(flat) == 55
    for (path, g), r, p in zip(flat, jax.tree_util.tree_leaves(r_grads),
                               jax.tree_util.tree_leaves(p_grads)):
        name = jax.tree_util.keystr(path)
        np.testing.assert_array_equal(g, r, err_msg=name)
        scale = max(float(jnp.max(jnp.abs(p))), 1e-12)
        assert float(jnp.max(jnp.abs(g - p))) <= 2e-6 * scale, name


def test_the_other_decoder_configuration_did_not_move():
    """``mellum2_share8``: the parameter tree at the benchmark's size, and
    at the rehearsal's size the tree, the step's program (its jaxpr, so
    every number of it, bit for bit) and the first step's numbers, are
    what the commit before this model gave (cb3acd4, read there), but for
    the V-trace recursion, an associative scan since PR 41: the two
    jaxprs' sequences of primitives differ in that one region (the
    ``scan`` of 31 steps against the levels' slices, multiplies and adds),
    and ``grad_norm`` by one unit in the last place; and but for the expert
    layer's gather and combine, two custom rules since PR 48 (at this size
    each is one walk of the whole buffer, the same gather and scatter-add
    behind a ``custom_vjp_call``) with one more counter,
    ``moe_rows_moved``: the numbers are the same."""

    def tree_hash(net):
        flat = jax.tree_util.tree_flatten_with_path(
            seeded_lm.param_shapes(net))[0]
        tree = [(jax.tree_util.keystr(p), tuple(l.shape), str(l.dtype))
                for p, l in flat]
        return hashlib.sha256(repr(tree).encode()).hexdigest()[:16], sum(
            int(np.prod(s)) for _, s, _ in tree)

    def config(*parts):
        with open(os.path.join(REPO, "benchmark", *parts)) as f:
            return json.load(f)

    full = config("configs", "mellum2_share8.json")
    assert tree_hash(program.build_model(full)) == (
        "e739e532df7d8bbd", 477798913)
    cfg = config("tests", "rehearsal_lm", "benchmark", "configs",
                 "tiny_lm.json")
    net = program.build_model(cfg)
    assert tree_hash(net)[0] == "75353ed6b87b019e"
    params = seeded_lm.make_params(seeded_lm.param_shapes(net), 7)
    batch = seeded_lm.make_learn_batch(7, cfg, 31, 1, 0.05, tiles=6)
    optimizer = program.build_optimizer(cfg)
    step = make_impala_train_step(
        learn_apply(net), optimizer, program.loss_config(cfg), mesh=None,
        donate=False,
    )
    state = make_train_state(params, optimizer)
    jaxpr = str(jax.make_jaxpr(lambda s, b: step(s, b))(state, batch))
    assert hashlib.sha256(jaxpr.encode()).hexdigest()[:16] == (
        "6ba4f485e5d50702")
    _, metrics = step(state, batch)
    assert "mtp_loss" not in metrics
    for name, value in (("total_loss", "0x1.6b148cp+0"),
                        ("grad_norm", "0x1.09a5fcp+1"),
                        ("pg_loss", "0x1.49388p+0"),
                        ("moe_assignments_held", "0x1.08p+6")):
        assert float(metrics[name]) == pytest.approx(
            float.fromhex(value), rel=1e-6), name


def test_this_configurations_step_did_not_move():
    """``glm47_flash_share8`` at the rehearsal's size: the parameter tree,
    the step's program (its jaxpr, but for the addresses of the policy
    functions it prints) and the first step's numbers are what the commit
    before the residual skeleton with several streams and the two head
    sizes gave (19a573a, read there): a description without ``residual``
    and with equal heads traces the program it traced, but for the
    V-trace recursion, an associative scan since PR 41: the two jaxprs'
    sequences of primitives differ in that one region, and ``total_loss``,
    a sum of cancelling terms, by 16 units in the last place (read
    here); and but for the expert layer's gather and combine, two custom
    rules and one more counter since PR 48, with the same numbers."""
    import re

    with open(os.path.join(REPO, "benchmark", "tests", "rehearsal_latent",
                           "benchmark", "configs", "tiny_latent.json")) as f:
        cfg = json.load(f)
    net = program.build_model(cfg)
    shapes = seeded_latent.param_shapes(net)
    tree = [(jax.tree_util.keystr(p), tuple(l.shape), str(l.dtype))
            for p, l in jax.tree_util.tree_flatten_with_path(shapes)[0]]
    assert hashlib.sha256(repr(tree).encode()).hexdigest()[:16] == (
        "23fb33e259ba39c3")
    params = seeded_latent.make_params(
        shapes, 7, cfg["model"]["kwargs"],
        cfg["seeding"]["correction_bias_scale"])
    batch = seeded_latent.make_learn_batch(7, cfg, 31, 1, 0.05)
    optimizer = program.build_optimizer(cfg)
    step = make_impala_train_step(
        learn_apply(net), optimizer, program.loss_config(cfg), mesh=None,
        donate=False,
    )
    state = make_train_state(params, optimizer)
    jaxpr = re.sub(r" at 0x[0-9a-f]+", "", str(
        jax.make_jaxpr(lambda s, b: step(s, b))(state, batch)))
    assert hashlib.sha256(jaxpr.encode()).hexdigest()[:16] == (
        "7e328b92b548fce8")
    _, metrics = step(state, batch)
    for name, value in (("total_loss", "0x1.df3a8cp-4"),
                        ("grad_norm", "0x1.2db8bcp+1"),
                        ("mtp_loss", "0x1.086d14p+2"),
                        ("moe_assignments_held", "0x1.7cp+6")):
        assert float(metrics[name]) == pytest.approx(
            float.fromhex(value), rel=1e-6), name
    assert not [k for k in metrics if k.startswith("hc_")]


def test_the_benchmarks_configuration_is_the_published_model():
    with open(os.path.join(
            REPO, "benchmark", "configs", "glm47_flash_share8.json")) as f:
        config = json.load(f)
    net = program.build_model(config)
    assert isinstance(net, DecoderLM)
    shapes = seeded_latent.param_shapes(net)
    count = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(shapes))
    assert count == 706_520_897
    p = shapes["params"]

    def size(tree):
        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(tree))

    assert size(p["block_0"]["attn"]) == 21_759_232
    assert size(p["block_0"]) == 84_677_888
    assert size(p["block_1"]) == 4 * 106_829_120
    assert size(p["embed"]) + size(p["head"]) == 79_298_560
    assert size(p["mtp"]) == 115_223_872
    assert set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert config["published"] == {
        "num_hidden_layers": 47, "n_routed_experts": 64, "vocab_size": 154880}
    kw = config["model"]["kwargs"]
    lat = kw["attention_kinds"]["latent"]["latent"]
    for key in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim"):
        assert lat[key] == config[key]
    assert (kw["hidden_size"], kw["intermediate_size"],
            kw["moe_intermediate_size"], kw["top_k"], kw["num_heads"],
            kw["num_experts"], kw["router"]["gate_scale"]) == (
        config["hidden_size"], config["intermediate_size"],
        config["moe_intermediate_size"], config["num_experts_per_tok"],
        config["num_attention_heads"], 64, config["routed_scaling_factor"])
