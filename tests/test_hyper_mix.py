"""The residual mixing's fused passes (``ops/hyper_mix.py``, in Pallas'
interpreter on the CPU) against the plain functions they replace
(``models/transformer.py``: ``hyper_coefficients``, ``hyper_read``,
``hyper_write``): values, counters and every gradient; the rule that picks
the path and the counter that records it; and what a rebuilt block with
several streams traces."""

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

from benchmark.lib import program, seeded_latent, seeded_lm  # noqa: E402
from benchmark.lib import seeded_mhc  # noqa: E402
from moolib_tpu.learner import ImpalaConfig, impala_loss  # noqa: E402
from moolib_tpu.models.lm import (Residual, _HyperMix, decoder_lm,  # noqa: E402
                                  learn_apply)
from moolib_tpu.models.transformer import (hyper_coefficients,  # noqa: E402
                                           hyper_read, hyper_write)
from moolib_tpu.ops import hyper_mix  # noqa: E402
from moolib_tpu.telemetry import global_telemetry  # noqa: E402

SPEC = dict(norm_eps=1e-6, sinkhorn_iters=20, eps=1e-6,
            res_clamp=(-30.0, 30.0))


mix_path = hyper_mix.mix_path  # the rule itself, whatever a test puts there


def tiles(shape, dtype):
    """:func:`hyper_mix.mix_path` as a TPU would answer it."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return mix_path(shape, dtype)


@pytest.fixture
def fused_on_the_cpu(monkeypatch):
    """The path a TPU would take, here: the kernels then run in Pallas'
    interpreter (``interpret`` follows the real platform)."""
    monkeypatch.setattr(hyper_mix, "mix_path", tiles)


def traced(path):
    return global_telemetry().registry.value(
        "residual_mix_calls_traced_total", path=path) or 0


def data(n, N, C, dtype, alpha_res=1.0, seed=0):
    """Streams, a sublayer's output, the mixing's parameters at the cell's
    seeding (``phi`` at variance 1/(n C), ``b`` at N(0, 0.5^2) with +2 on
    the remix matrix's diagonal) and weights for a scalar of the outputs."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    k = n * n + 2 * n
    X = jax.random.normal(ks[0], (n, N, C), jnp.float32).astype(dtype)
    y = jax.random.normal(ks[1], (N, C), jnp.float32).astype(dtype)
    phi = jax.random.normal(ks[2], (n * C, k)) / np.sqrt(n * C)
    b = 0.5 * jax.random.normal(ks[3], (k,))
    b = b.at[2 * n:].add(2.0 * jnp.eye(n).reshape(-1))
    alpha = jnp.asarray([1.0, 1.0, alpha_res])
    w_out = jax.random.normal(ks[4], (n, N, C), jnp.float32)
    w_h = jax.random.normal(ks[5], (N, C), jnp.float32)
    return (X, y, phi, b, alpha), (w_out, w_h)


def sublayer(fused, weights, X, y, phi, b, alpha):
    """One sublayer whose function of ``h`` is ``y + 0.1 h``: a scalar of
    the new streams and ``h``, and the values beside it."""
    n, N, _ = X.shape
    if fused:
        h, X, coef, counters = hyper_mix.read(
            X, phi, b, alpha, SPEC["norm_eps"], SPEC["sinkhorn_iters"],
            SPEC["eps"], SPEC["res_clamp"])
    else:
        pre, post, res, counters = hyper_coefficients(
            X, phi, b, alpha, **SPEC)
        h = hyper_read(X, pre)
        coef = jnp.concatenate([pre, post, res.reshape(n * n, N)])
    y = (y.astype(jnp.float32) + 0.1 * h).astype(y.dtype)
    out = (hyper_mix.write if fused else hyper_write)(X, coef, y)
    w_out, w_h = weights
    value = jnp.sum(out.astype(jnp.float32) * w_out) + jnp.sum(h * w_h)
    return value, (h, out, coef, counters)


def worst(a, b):
    a, b = (np.asarray(t, np.float32) for t in (a, b))
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


@pytest.mark.parametrize("seeding", ["as_the_cell", "at_the_clip"])
@pytest.mark.parametrize("n", [4, 2])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_fused_passes_are_the_plain_functions(dtype, n, seeding):
    """Three tiles of 128 tokens (``dphi`` is summed over them), a width of
    two lane tiles: ``h``, the new streams, the coefficients, the three
    counters and the gradients of the streams, ``y``, ``phi``, ``b`` and
    ``alpha``. float32 streams to float32's rounding; bfloat16 streams to
    bfloat16's on what is stored in it (the kernels round the streams'
    gradient once where the plain path sums three rounded parts) and to a
    thousandth on the float32 parameters' gradients."""
    dtype = jnp.dtype(dtype)
    args, weights = data(n, 384, 256, dtype,
                         alpha_res=40.0 if seeding == "at_the_clip" else 1.0)
    assert tiles(args[0].shape, dtype) == "fused"
    results = []
    for fused in (True, False):
        (value, aux), grads = jax.value_and_grad(
            functools.partial(sublayer, fused, weights),
            argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
        results.append((value, aux, grads))
    (value, (h, out, coef, counters), grads), (
        p_value, (p_h, p_out, p_coef, p_counters), p_grads) = results
    exact = dtype == jnp.float32
    stored = 3e-5 if exact else 2.0 ** -7
    assert worst(h, p_h) < 3e-6
    assert worst(coef, p_coef) < 3e-5
    assert worst(out, p_out) < stored
    assert float(value) == pytest.approx(float(p_value), rel=stored)
    assert float(counters["hc_res_clamped"]) == float(
        p_counters["hc_res_clamped"])
    if seeding == "at_the_clip":
        assert float(counters["hc_res_clamped"]) > 0
    else:
        assert 0.003 < float(counters["hc_row_sum_gap"]) < 0.05
    for key in ("hc_row_sum_gap", "hc_col_sum_gap"):
        assert float(counters[key]) == pytest.approx(
            float(p_counters[key]), rel=1e-3, abs=3e-7), key
    for name, g, p, tol in zip(
            ("streams", "y", "phi", "b", "alpha"), grads, p_grads,
            (stored, stored, 1e-4 if exact else 1e-3,
             1e-4 if exact else 1e-3, 1e-4 if exact else 1e-3)):
        assert g.dtype == p.dtype and g.shape == p.shape, name
        assert np.all(np.isfinite(np.asarray(g, np.float32))), name
        assert float(jnp.max(jnp.abs(p.astype(jnp.float32)))) > 0, name
        assert worst(g, p) < tol, (name, worst(g, p))


def test_a_matrix_wholly_outside_the_clip_passes_no_gradient():
    """A clip so narrow that every entry of the remix matrix stands at it:
    the matrix is a constant, the kernel counts every entry, and
    ``alpha``'s third scale gets exactly nothing, as in
    ``test_the_gradient_is_finite_at_the_clip_and_zero_outside_it``."""
    (X, y, phi, b, alpha), _ = data(4, 128, 128, jnp.float32, alpha_res=40.0)
    phi = phi.at[:, 8:].mul(50.0)

    def loss(alpha):
        h, _, coef, counters = hyper_mix.read(
            X, phi, b, alpha, 1e-6, 20, 1e-6, (-1e-3, 1e-3))
        return jnp.sum(coef * jnp.arange(24.0)[:, None]) + jnp.sum(h), counters

    (_, counters), g_alpha = jax.value_and_grad(loss, has_aux=True)(alpha)
    assert float(counters["hc_res_clamped"]) > 15 * 128
    assert float(g_alpha[2]) == pytest.approx(0.0, abs=1e-6)
    assert abs(float(g_alpha[0])) > 1e-3


@pytest.mark.parametrize("shape,dtype,path", [
    ((4, 4096, 3584), "bfloat16", "fused"),   # the cell's
    ((4, 4096, 3584), "float32", "fused"),
    ((2, 384, 128), "bfloat16", "fused"),
    ((4, 384, 96), "bfloat16", "plain"),      # no whole lane tile
    ((4, 100, 128), "bfloat16", "plain"),     # no tile of tokens
    ((3, 384, 128), "bfloat16", "plain"),     # 15 coefficient rows
    ((6, 384, 128), "bfloat16", "plain"),     # 3 x 48 columns of phi
    ((4, 384, 128), "float16", "plain"),
    ((4, 128, 8192), "bfloat16", "plain"),    # a tile would not fit VMEM
])
def test_the_path_follows_from_shapes_dtype_and_platform(shape, dtype, path):
    assert tiles(shape, jnp.dtype(dtype)) == path
    assert hyper_mix.mix_path(shape, jnp.dtype(dtype)) == "plain"  # the CPU


@pytest.mark.parametrize("N,C,path", [
    (256, 128, "fused"), (256, 96, "plain"), (200, 128, "plain"),
])
def test_a_sublayers_read_side_records_its_path(fused_on_the_cpu, N, C, path):
    """``_HyperMix`` on streams that tile and on two that do not: the
    counter names the path taken, and either path gives the plain
    functions' ``h`` and coefficients."""
    spec = Residual(4, 20, 1e-6, (-30.0, 30.0))
    mix = _HyperMix(spec, 1e-6)
    (X, _, phi, b, alpha), _ = data(4, N, C, jnp.bfloat16)
    params = {"params": {"phi": phi, "b": b, "alpha": alpha}}
    before = {p: traced(p) for p in ("fused", "plain")}
    (h, streams, coef), sown = mix.apply(
        params, X, mutable=["intermediates"])
    other = "plain" if path == "fused" else "fused"
    assert traced(path) - before[path] == 1
    assert traced(other) == before[other]
    pre, post, res, counters = hyper_coefficients(
        X, phi, b, alpha, norm_eps=1e-6, sinkhorn_iters=20, eps=1e-6,
        res_clamp=(-30.0, 30.0))
    assert streams is X or bool(jnp.all(streams == X))
    assert worst(h, hyper_read(X, pre)) < 3e-6
    assert worst(coef, jnp.concatenate([pre, post, res.reshape(16, N)])) < 3e-5
    (got,) = sown["intermediates"]["hc_counters"]
    assert float(got["hc_row_sum_gap"]) == pytest.approx(
        float(counters["hc_row_sum_gap"]), rel=1e-3)


# xing4_share8's tiny twin (benchmark/tests/rehearsal_mhc) at a width of
# one lane tile and 128 tokens, so that its six mixed sublayers tile
VOCAB, T, B = 48, 63, 2
LOSS = {"discounting": 0.99, "baseline_cost": 0.5, "entropy_cost": 0.0006,
        "reward_clip": 1.0}
RESIDUAL = {"streams": 4, "sinkhorn_iters": 20, "eps": 1e-6,
            "res_clamp": [-30, 30]}
MODEL = dict(
    vocab_size=VOCAB, hidden_size=128,
    layers=[{"attention": "latent", "mlp": "dense"},
            {"attention": "latent", "mlp": "sparse", "repeat": 2}],
    attention_kinds={"latent": {
        "window": None, "rope": {"theta": 10000.0},
        "latent": {"q_lora_rank": 12, "kv_lora_rank": 8,
                   "qk_nope_head_dim": 12, "qk_rope_head_dim": 4,
                   "v_head_dim": 8},
    }},
    num_heads=3, num_kv_heads=3, head_dim=16, num_experts=8,
    experts_held=[2, 4], top_k=2, moe_intermediate_size=24,
    router={"scoring": "sigmoid", "selection_bias": True, "gate_scale": 2.0},
    shared_expert_size=24, intermediate_size=40, remat_blocks=True,
    rms_norm_eps=1e-6, attention_backend="dense", residual=RESIDUAL,
)


def twin(seed=7, **over):
    model = dict(MODEL, **over)
    net = decoder_lm(**model)
    params = seeded_latent.make_params(
        seeded_latent.param_shapes(net), seed, model, 0.05)
    if model.get("residual"):
        params = seeded_mhc.seed_mixing(params, seed, 4, 0.5, 2.0)
    config = {"num_actions": VOCAB,
              "observation": {"vocab": VOCAB, "zipf_s": 1.0}}
    batch = seeded_lm.make_learn_batch(seed, config, T, B, 0.0)
    done = np.zeros((T + 1, B), bool)
    done[[13, 40], :] = True
    return net, params, dict(batch, done=jnp.asarray(done))


def loss_and_grad(net, params, batch):
    return jax.value_and_grad(impala_loss, has_aux=True)(
        params, learn_apply(net), batch, ImpalaConfig(**LOSS))


def kernels_and_checkpoints(jaxpr, inside=False, found=None):
    """The mixing's Pallas calls of a jaxpr by kernel, and the
    ``checkpoint`` equations nested inside another one."""
    found = found if found is not None else {"nested_checkpoints": 0}
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            kernel = eqn.params["name"]
            found[kernel] = found.get(kernel, 0) + 1
        if name == "checkpoint" and inside:
            found["nested_checkpoints"] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            kernels_and_checkpoints(
                sub, inside or name == "checkpoint", found)
    return found


def test_a_rebuilt_block_runs_the_mixings_forward_twice_and_backward_once(
        fused_on_the_cpu):
    """The gradient program of the twin: ``block_0`` and the scan's body
    are two mixed sublayers each, so the jaxpr holds four. Each runs its
    read side's forward kernel twice (the forward pass, the block's
    rebuild) and each of the two backward kernels once; the write side's
    forward kernel is rebuilt only where something reads the new streams
    (the first sublayer of a block: nothing in a block's backward pass
    reads its output). The block's rebuild holds no ``checkpoint`` of its
    own. Loss and gradients are the plain path's."""
    net, params, batch = twin()
    jaxpr = jax.make_jaxpr(
        lambda p: loss_and_grad(net, p, batch)[1])(params)
    found = kernels_and_checkpoints(jaxpr.jaxpr)
    assert found == {
        "hyper_mix_read": 8, "hyper_mix_write": 6, "hyper_mix_write_bwd": 4,
        "hyper_mix_read_bwd": 4, "nested_checkpoints": 0,
    }


def test_the_twins_step_on_the_fused_path_is_the_plain_paths(monkeypatch):
    """Loss and every gradient leaf of the twin through the kernels
    (float32 streams: the twin computes in float32) against the plain
    functions; the counter reads the six sublayers traced as fused (two of
    ``block_0``, the scan's body twice, as flax traces it) where the plain
    trace and a description with one stream leave it where it was."""
    net, params, batch = twin()
    before = traced("fused"), traced("plain")
    (p_loss, p_metrics), p_grads = loss_and_grad(net, params, batch)
    assert traced("fused") == before[0] and traced("plain") > before[1]
    monkeypatch.setattr(hyper_mix, "mix_path", tiles)
    (loss, metrics), grads = loss_and_grad(net, params, batch)
    assert traced("fused") - before[0] == 6
    assert float(loss) == pytest.approx(float(p_loss), rel=1e-5)
    for key in ("hc_row_sum_gap", "hc_col_sum_gap", "hc_res_clamped"):
        assert float(metrics[key]) == pytest.approx(
            float(p_metrics[key]), rel=1e-3, abs=3e-7), key
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    mixing = 0
    for (path, g), p in zip(flat, jax.tree_util.tree_leaves(p_grads)):
        name = jax.tree_util.keystr(path)
        mixing += "hc_" in name
        scale = max(float(jnp.max(jnp.abs(p))), 1e-12)
        assert float(jnp.max(jnp.abs(g - p))) <= 2e-4 * scale, name
    assert mixing == 12
    # one stream: neither path of the mixing is traced at all
    counts = traced("fused"), traced("plain")
    one, one_params, _ = twin(residual=None)
    loss_and_grad(one, one_params, batch)
    assert (traced("fused"), traced("plain")) == counts


def test_the_cells_step_traces_every_sublayer_as_fused(monkeypatch):
    """``xing4_share8`` as the benchmark builds it, traced (nothing runs)
    as a TPU would trace it: every mixed sublayer of the step, the dense
    block's two and the four scanned blocks' two, takes the kernels."""
    with open(os.path.join(
            REPO, "benchmark", "configs", "xing4_share8.json")) as f:
        config = json.load(f)
    net = program.build_model(config)
    shapes = seeded_latent.param_shapes(net)
    obs = jax.ShapeDtypeStruct((4096, 1), jnp.int32)
    done = jax.ShapeDtypeStruct((4096, 1), jnp.bool_)
    monkeypatch.setattr(hyper_mix, "mix_path", tiles)
    before = traced("fused"), traced("plain")
    jax.eval_shape(lambda p, o, d: net.apply(p, o, d, ()), shapes, obs, done)
    assert traced("plain") == before[1]
    assert traced("fused") - before[0] == 2 + 2 * 2  # flax: the body twice
