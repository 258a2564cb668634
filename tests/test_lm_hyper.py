"""The decoder on a residual skeleton with several streams, mixed by
Sinkhorn-normalised matrices, and latent attention whose value head is
narrower than its query/key head under YaRN's score scale (``models/lm.py``,
``models/transformer.py``, ``ops/attention.py``) against their plain
reference (``benchmark/reference/xing4_share8.py``: float32 ``jax.numpy``
from the equations, nothing of the program), on the CPU at tiny sizes with
seeded weights; the flash kernels at two head sizes against plain
attention in Pallas' interpreter; and the benchmark's configuration at its
published widths."""

import functools
import json
import math
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
from benchmark.lib import seeded_lm, seeded_mhc  # noqa: E402
from benchmark.reference import xing4_share8 as ref  # noqa: E402
from benchmark.reference import xing4_tiny  # noqa: E402
from moolib_tpu.learner import ImpalaConfig, impala_loss  # noqa: E402
from moolib_tpu.models.lm import (DecoderLM, decoder_lm,  # noqa: E402
                                  learn_apply, rope_inv_freq, Rope)
from moolib_tpu.models.transformer import hyper_coefficients  # noqa: E402
from moolib_tpu.ops import attention as attn_ops  # noqa: E402
from moolib_tpu.ops.attention import (blockwise_attention,  # noqa: E402
                                      dense_attention, flash_attention)
from moolib_tpu.telemetry import global_telemetry  # noqa: E402

VOCAB, T, B = 48, 31, 2
LOSS = {"discounting": 0.99, "baseline_cost": 0.5, "entropy_cost": 0.0006,
        "reward_clip": 1.0}
CAST = reference_train.identity_cast
SCALE = 16 ** -0.5 * (0.1 * math.log(4.0) + 1) ** 2
RESIDUAL = {"streams": 4, "sinkhorn_iters": 20,
            "eps": 1e-6, "res_clamp": [-30, 30]}
MODEL = dict(
    vocab_size=VOCAB, hidden_size=32,
    layers=[{"attention": "latent", "mlp": "dense"},
            {"attention": "latent", "mlp": "sparse", "repeat": 2}],
    attention_kinds={"latent": {
        "window": None,
        "rope": {"theta": 10000.0, "factor": 4.0,
                 "original_max_position_embeddings": 16},
        "latent": {"q_lora_rank": 12, "kv_lora_rank": 8,
                   "qk_nope_head_dim": 12, "qk_rope_head_dim": 4,
                   "v_head_dim": 8, "softmax_scale": SCALE},
    }},
    num_heads=3, num_kv_heads=3, head_dim=16, num_experts=8,
    experts_held=[2, 4], top_k=2, moe_intermediate_size=24,
    router={"scoring": "sigmoid", "selection_bias": True, "gate_scale": 2.0},
    shared_expert_size=24, intermediate_size=40, remat_blocks=True,
    rms_norm_eps=1e-6, residual=RESIDUAL,
)


def tiny(**over):
    model = dict(MODEL, **over)
    return decoder_lm(**model), model


def inputs(net, model, seed, done_at=(13, 27)):
    params = seeded_mhc.seed_mixing(
        seeded_latent.make_params(
            seeded_latent.param_shapes(net), seed, model, 0.05),
        seed, RESIDUAL["streams"], 0.5, 2.0,
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


def reference_loss_and_grad(params, batch):
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.value_and_grad(
            xing4_tiny.loss_fn, has_aux=True
        )(params, batch, LOSS, CAST)
    return loss, grads


def program_loss_and_grad(net, params, batch):
    return jax.value_and_grad(impala_loss, has_aux=True)(
        params, learn_apply(net), batch, ImpalaConfig(**LOSS)
    )


def test_logits_and_baseline_match_the_reference(whole):
    net, _, params, batch = whole
    (logits, baseline), _ = net.apply(params, batch["obs"], batch["done"], ())
    with jax.default_matmul_precision("highest"):
        r_logits, r_baseline, _ = xing4_tiny.forward(
            params, batch["obs"], batch["done"], (), CAST)
    assert logits.shape == (T + 1, B, VOCAB)
    close(logits, r_logits, 2e-5)
    close(baseline, r_baseline, 2e-5)
    # the parameter tree: two mixers a block, stacked where the block is
    p = params["params"]
    assert p["block_0"]["hc_attn"]["phi"].shape == (4 * 32, 24)
    assert p["block_1"]["hc_mlp"]["phi"].shape == (2, 4 * 32, 24)
    assert p["block_0"]["hc_mlp"]["alpha"].shape == (3,)
    # the value head is narrower than the query/key head
    assert p["block_0"]["attn"]["kv_b"]["kernel"].shape == (8, 3 * (12 + 8))
    assert p["block_0"]["attn"]["o"]["kernel"].shape == (3 * 8, 32)


def test_the_whole_learner_step_matches_the_reference(whole):
    """Loss and every gradient leaf, the mixing's own among them, through
    20 Sinkhorn iterations, the scan and the rebuilt blocks."""
    net, _, params, batch = whole
    (loss, metrics), grads = program_loss_and_grad(net, params, batch)
    r_loss, r_grads = reference_loss_and_grad(params, batch)
    assert float(loss) == pytest.approx(float(r_loss), rel=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    assert len(flat) == 46
    mixing = 0
    for (path, g), r in zip(flat, jax.tree_util.tree_leaves(r_grads)):
        name = jax.tree_util.keystr(path)
        scale = max(float(jnp.max(jnp.abs(r))), 1e-12)
        assert float(jnp.max(jnp.abs(g - r))) <= 2e-4 * scale, name
        if "hc_" in name:
            mixing += 1
            assert float(jnp.max(jnp.abs(r))) > 0, name
    assert mixing == 12
    # the mixing's counters reach the step's metrics: a gap is a maximum
    # over sublayers and tokens, the clip's count a sum
    assert 0 < float(metrics["hc_row_sum_gap"]) < 0.05
    assert float(metrics["hc_col_sum_gap"]) < 1e-5
    assert float(metrics["hc_res_clamped"]) == 0
    assert "mtp_loss" not in metrics


@pytest.mark.parametrize("fault", [
    "no_iterations", "one_iteration", "post_gate_without_its_2",
    "no_score_scale", "plain_rotary",
])
def test_the_seeding_sees_what_a_program_leaves_out(whole, fault):
    """At the seeded scales (``b`` at N(0, 0.5^2) with +2 on the remix
    matrix's diagonal, ``phi`` at variance 1/(n d)) a model that leaves
    out a part of the mathematics is far from the reference: its loss by
    more than a thousandth, where the sound program's is within 1e-5."""
    net, model, params, batch = whole
    r_loss, _ = reference_loss_and_grad(params, batch)
    kinds = json.loads(json.dumps(model["attention_kinds"]))
    if fault == "no_iterations":
        broken = tiny(residual=dict(RESIDUAL, sinkhorn_iters=0))[0]
    elif fault == "one_iteration":
        broken = tiny(residual=dict(RESIDUAL, sinkhorn_iters=1))[0]
    elif fault == "no_score_scale":
        kinds["latent"]["latent"].pop("softmax_scale")
        broken = tiny(attention_kinds=kinds)[0]
    elif fault == "plain_rotary":
        kinds["latent"]["rope"] = {"theta": 10000.0}
        broken = tiny(attention_kinds=kinds)[0]
    else:
        broken = net
    if fault == "post_gate_without_its_2":
        # 2 sigmoid(z) = sigmoid(z) where the write-back is halved
        from moolib_tpu.models import transformer

        def halved(*a, **kw):
            pre, post, res, counters = hyper_coefficients(*a, **kw)
            return pre, post / 2.0, res, counters

        with pytest.MonkeyPatch.context() as mp:
            mp.setattr("moolib_tpu.models.lm.hyper_coefficients", halved)
            assert transformer.hyper_coefficients is hyper_coefficients
            (loss, _), _ = program_loss_and_grad(broken, params, batch)
    else:
        (loss, _), _ = program_loss_and_grad(broken, params, batch)
    gap = abs(float(loss) - float(r_loss)) / abs(float(r_loss))
    assert gap > 1e-3, (fault, gap)


def coefficients(alpha_res=1.0, seed=0, N=64, n=4, C=16, **over):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    X = jax.random.normal(ks[0], (n, N, C), jnp.float32)
    phi = jax.random.normal(ks[1], (n * C, n * n + 2 * n)) / np.sqrt(n * C)
    b = 0.5 * jax.random.normal(ks[2], (n * n + 2 * n,))
    alpha = jnp.asarray([1.0, 1.0, alpha_res])
    kw = dict(norm_eps=1e-6, sinkhorn_iters=20, eps=1e-6,
              res_clamp=(-30.0, 30.0))
    kw.update(over)
    return X, phi, b, alpha, kw


def test_the_remix_matrix_is_doubly_stochastic_after_20_iterations():
    X, phi, b, alpha, kw = coefficients(alpha_res=0.5)
    pre, post, res, counters = hyper_coefficients(X, phi, b, alpha, **kw)
    assert res.shape == (4, 4, 64) and pre.shape == post.shape == (4, 64)
    assert float(jnp.max(jnp.abs(res.sum(axis=1) - 1))) < 1e-4  # rows
    assert float(jnp.max(jnp.abs(res.sum(axis=0) - 1))) < 1e-4  # columns
    assert float(counters["hc_row_sum_gap"]) < 1e-4
    assert float(counters["hc_col_sum_gap"]) < 1e-4
    assert float(jnp.min(res)) > 0
    assert float(jnp.min(pre)) > 0 and float(jnp.max(pre)) < 1
    assert float(jnp.min(post)) > 0 and float(jnp.max(post)) < 2
    # one iteration is not enough, and none leaves the exponentials
    _, _, once, c1 = hyper_coefficients(
        X, phi, b, alpha, **dict(kw, sinkhorn_iters=1))
    assert float(c1["hc_row_sum_gap"]) > 1e-2
    # against the reference's own mixing, which lays the tokens first
    r_pre, r_post, r_res = ref.mixing(
        X.transpose(1, 0, 2), {"phi": phi, "b": b, "alpha": alpha},
        dict(xing4_tiny.TINY, streams=4))
    close(pre.T, r_pre, 1e-5)
    close(post.T, r_post, 1e-5)
    close(res.transpose(2, 0, 1), r_res, 1e-5)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_at_the_cells_seeding_20_iterations_leave_the_rows_a_hundredth_off(
        seed):
    """What ``hc_row_sum_gap`` reads where the cell measures: ``m`` at
    N(0, 1), ``b`` at the configuration's scale with its weight on the
    diagonal, 4,096 tokens. The iterations end on the columns, which then
    sum to 1; the worst token's rows are 0.8-3.5% off after 20 (the chip
    reads 0.013-0.025 over ten sublayers), more after 19 and less after
    21: a change to the count of iterations, or to which normalisation
    comes last, moves this reading."""
    with open(os.path.join(
            REPO, "benchmark", "configs", "xing4_share8.json")) as f:
        seeding = json.load(f)["seeding"]
    X, phi, b, alpha, kw = coefficients(seed=seed, N=4096)
    b = b * (seeding["hc_b_scale"] / 0.5)
    b = b.at[8:].add(seeding["hc_res_diagonal"] * jnp.eye(4).reshape(-1))

    def gaps(iters):
        counters = hyper_coefficients(
            X, phi, b, alpha, **dict(kw, sinkhorn_iters=iters))[3]
        return (float(counters["hc_row_sum_gap"]),
                float(counters["hc_col_sum_gap"]))

    rows, columns = gaps(20)
    assert 0.008 < rows < 0.035 and columns < 1e-5
    assert gaps(19)[0] > rows > gaps(21)[0]
    assert gaps(200)[0] < 1e-4


def test_the_gradient_is_finite_at_the_clip_and_zero_outside_it():
    """Scales large enough that entries stand at the clip on both sides:
    the gradient through exp(clip(.)) and 20 iterations stays finite, the
    entries at the clip are counted, and a matrix wholly outside the clip
    passes no gradient to what made it."""
    X, phi, b, alpha, kw = coefficients(alpha_res=40.0)

    def loss(phi, alpha, clamp=(-30.0, 30.0)):
        pre, post, res, counters = hyper_coefficients(
            X, phi, b, alpha, **dict(kw, res_clamp=clamp))
        weights = jnp.arange(16.0).reshape(4, 4, 1)
        return jnp.sum(res * weights) + jnp.sum(pre) + jnp.sum(post), counters

    (value, counters), (g_phi, g_alpha) = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(phi, alpha)
    assert np.isfinite(float(value))
    assert 0 < float(counters["hc_res_clamped"]) < 16 * 64
    assert np.all(np.isfinite(np.asarray(g_phi)))
    assert np.all(np.isfinite(np.asarray(g_alpha)))
    assert float(jnp.max(jnp.abs(g_phi[:, 8:]))) > 0
    # a clip so narrow that every entry stands at it: the remix matrix is
    # a constant and its part of the gradient exactly zero
    (_, counters), (g_phi, g_alpha) = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(phi, alpha, (-1e-3, 1e-3))
    assert float(counters["hc_res_clamped"]) > 15 * 64
    assert float(g_alpha[2]) == pytest.approx(0.0, abs=1e-6)


def qkv(seed=0, Bq=2, H=4, Hkv=4, Tq=64, D=24, Dv=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (Bq, H, Tq, D), jnp.float32)
    k = jax.random.normal(ks[1], (Bq, Hkv, Tq, D), jnp.float32)
    v = jax.random.normal(ks[2], (Bq, Hkv, Tq, Dv), jnp.float32)
    seg = jnp.cumsum(
        jax.random.uniform(ks[3], (Bq, Tq)) < 0.05, axis=1
    ).astype(jnp.int32)
    return q, k, v, seg


@pytest.mark.parametrize("Hkv,window", [(4, None), (2, None), (4, 20)])
@pytest.mark.parametrize("what", ["forward", "dq", "dk", "dv"])
def test_flash_kernels_with_a_narrower_value_head(what, Hkv, window):
    """The forward and the backward kernel in Pallas' interpreter at a
    query/key head of 24 and a value head of 16, with segments and a score
    scale of their own, against plain attention; grouped heads and a
    window besides."""
    q, k, v, seg = qkv(Hkv=Hkv)
    kw = dict(causal=True, segment_ids=seg, window=window, scale=0.3)
    weights = jax.random.normal(jax.random.PRNGKey(9), (2, 4, 64, 16))

    def flash(q, k, v):
        return flash_attention(q, k, v, block_q=16, block_k=32,
                               interpret=True, **kw)

    def plain(q, k, v):
        return dense_attention(q, k, v, **kw)

    if what == "forward":
        out = flash(q, k, v)
        assert out.shape == (2, 4, 64, 16)
        close(out, plain(q, k, v), 2e-5)
        close(blockwise_attention(q, k, v, block_k=16, **kw),
              plain(q, k, v), 2e-5)
        # the scale is the scores': another one is another result
        other = flash_attention(q, k, v, block_q=16, block_k=32,
                                interpret=True, **dict(kw, scale=None))
        assert float(jnp.max(jnp.abs(other - out))) > 1e-3
        return
    arg = {"dq": 0, "dk": 1, "dv": 2}[what]
    grads = [
        jax.grad(lambda *a: jnp.sum(fn(*a) * weights), argnums=arg)(q, k, v)
        for fn in (flash, plain)
    ]
    assert grads[0].shape == (q, k, v)[arg].shape
    close(grads[0], grads[1], 5e-5)


def test_a_rebuilt_block_with_two_head_sizes_runs_no_forward_kernel(
        monkeypatch):
    """The model through the flash kernels (interpreted) with rebuilt
    blocks: every flash call of the trace counts under
    ``attention_cores_kept_total``, and loss and gradients are the dense
    backend's."""
    monkeypatch.setattr(attn_ops, "flash_attention", functools.partial(
        attn_ops.flash_attention, interpret=True))
    registry = global_telemetry().registry

    def counts():
        return (
            registry.value("attention_cores_kept_total") or 0,
            registry.value("attention_calls_traced_total", backend="flash")
            or 0,
        )

    net, model = tiny(attention_backend="flash", attention_block=16)
    params, batch = inputs(net, model, 7)
    kept, calls = counts()
    (loss, _), grads = program_loss_and_grad(net, params, batch)
    after = counts()
    assert after[0] - kept == after[1] - calls >= 2
    plain, _ = tiny(attention_backend="dense")
    (d_loss, _), d_grads = program_loss_and_grad(plain, params, batch)
    assert float(loss) == pytest.approx(float(d_loss), rel=1e-5)
    for (path, g), d in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree_util.tree_leaves(d_grads)):
        scale = max(float(jnp.max(jnp.abs(d))), 1e-12)
        assert float(jnp.max(jnp.abs(g - d))) <= 2e-4 * scale, (
            jax.tree_util.keystr(path))


def test_yarn_inside_latent_attention_is_the_references():
    """The program's YaRN frequencies over the rotary part alone, against
    the reference's own blend, at the published numbers and the tiny
    ones."""
    for spec, rope in (
        (ref.PUBLISHED, Rope(10000.0, 64.0, 4096)),
        (xing4_tiny.TINY, Rope(10000.0, 4.0, 16)),
    ):
        rot = spec["qk_rope_head_dim"]
        close(rope_inv_freq(rope, rot), ref.yarn_inv_freq(spec), 1e-6)
    inv = rope_inv_freq(Rope(10000.0, 64.0, 4096), 64)
    plain = rope_inv_freq(Rope(10000.0), 64)
    assert inv[0] == plain[0] and inv[-1] == pytest.approx(plain[-1] / 64)
    mscale = 0.1 * math.log(64.0) + 1.0
    assert mscale == pytest.approx(1.41589, abs=1e-5)


def test_streams_and_a_module_together_are_refused():
    net, model = tiny(mtp={"attention": "latent", "mlp": "sparse"})
    with pytest.raises(ValueError, match="not built"):
        seeded_latent.param_shapes(net)
    # the description states every number of the skeleton: none is the
    # program's to default
    for key in RESIDUAL:
        with pytest.raises((TypeError, KeyError), match=key):
            tiny(residual={k: v for k, v in RESIDUAL.items() if k != key})


def test_the_benchmarks_configuration_is_the_published_model():
    with open(os.path.join(
            REPO, "benchmark", "configs", "xing4_share8.json")) as f:
        config = json.load(f)
    net = program.build_model(config)
    assert isinstance(net, DecoderLM)
    shapes = seeded_latent.param_shapes(net)

    def size(tree):
        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(tree))

    p = shapes["params"]
    assert size(shapes) == 759_350_031
    assert size(p["block_0"]["attn"]) == 28_411_136
    assert size(p["block_0"]["hc_attn"]) + size(p["block_0"]["hc_mlp"]) == (
        688_182)
    assert size(p["block_0"]) == 128_196_918
    assert size(p["block_1"]) == 4 * 128_426_358
    assert size(p["embed"]) + size(p["head"]) == 117_440_512
    assert size(p["final_norm"]) + size(p["baseline"]) == 7_169
    assert "mtp" not in p
    assert set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"}
    assert config["published"] == {
        "num_hidden_layers": 40, "n_routed_experts": 64,
        "vocab_size": 131072, "num_nextn_predict_layers": 1}
    kw = config["model"]["kwargs"]
    lat = kw["attention_kinds"]["latent"]["latent"]
    for key in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim"):
        assert lat[key] == config[key]
    scaling = config["rope_scaling"]
    mscale = 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1
    assert lat["softmax_scale"] == pytest.approx(
        (lat["qk_nope_head_dim"] + lat["qk_rope_head_dim"]) ** -0.5
        * mscale ** 2, rel=1e-12)
    rope = kw["attention_kinds"]["latent"]["rope"]
    assert (rope["theta"], rope["factor"],
            rope["original_max_position_embeddings"], rope["beta_fast"],
            rope["beta_slow"]) == (
        config["rope_theta"], scaling["factor"],
        scaling["original_max_position_embeddings"], scaling["beta_fast"],
        scaling["beta_slow"])
    res = kw["residual"]
    assert (res["streams"], res["sinkhorn_iters"], res["eps"],
            res["res_clamp"]) == (
        config["hc_mult"], config["hc_sinkhorn_iters"], config["hc_eps"],
        [config["mhc_h_res_clamp_min"], config["mhc_h_res_clamp_max"]])
    assert (kw["hidden_size"], kw["intermediate_size"],
            kw["moe_intermediate_size"], kw["shared_expert_size"],
            kw["top_k"], kw["num_heads"], kw["num_experts"],
            kw["router"]["gate_scale"], kw["rms_norm_eps"]) == (
        config["hidden_size"], config["intermediate_size"],
        config["moe_intermediate_size"],
        config["n_shared_experts"] * config["moe_intermediate_size"],
        config["num_experts_per_tok"], config["num_attention_heads"],
        config["router_width"], config["routed_scaling_factor"],
        config["rms_norm_eps"])
    assert kw["moe_buffer_rows"] == int(2.5 * 4096 * 4 * 8 / 64)
    # the reference's published settings are the file's
    for key, value in (("qk_nope_head_dim", 128), ("qk_rope_head_dim", 64),
                       ("v_head_dim", 128), ("top_k", 4),
                       ("routed_scaling_factor", 2.0), ("eps", 1e-6),
                       ("theta", 10000.0), ("yarn_factor", 64.0),
                       ("yarn_original", 4096), ("streams", 4),
                       ("sinkhorn_iters", 20), ("hc_eps", 1e-6),
                       ("res_clamp", (-30.0, 30.0))):
        assert ref.PUBLISHED[key] == value, key
