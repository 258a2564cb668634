"""Chunk-summary attention (a query reads its own window exactly and every
earlier window through one learned summary a chunk, the two key sets under
one softmax), the norm with a unit offset and the eight prediction heads
(``models/lm.py``, ``ops/attention.py``) against their plain reference
(``benchmark/reference/evabyte_pp8.py``: float32 ``jax.numpy`` from the
equations, nothing of the program), on the CPU at tiny sizes with seeded
weights; the two flash calls and their merge against plain attention in
Pallas' interpreter; and the benchmark's configuration at its published
widths."""

import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.lib import counts_eva, program, reference_latent  # noqa: E402
from benchmark.lib import reference_train, seeded_eva, seeded_lm  # noqa: E402
from benchmark.reference import evabyte_pp8, evabyte_tiny  # noqa: E402
from moolib_tpu.learner import (ImpalaConfig, impala_loss,  # noqa: E402
                                make_impala_train_step, make_train_state)
from moolib_tpu.models.lm import (RMSNorm, decoder_lm,  # noqa: E402
                                  eva_ids, eva_pair_counts, eva_summaries,
                                  learn_apply)
from moolib_tpu.ops import attention as attn_ops  # noqa: E402
from moolib_tpu.ops.attention import (dense_attention,  # noqa: E402
                                      flash_attention, merge_attention)

VOCAB, B, W, C = 20, 2, 8, 2
LOSS = {"discounting": 0.99, "baseline_cost": 0.5, "entropy_cost": 0.0006,
        "reward_clip": 1.0, "mtp_cost": 0.1}
SEEDING = {"phi_scale": 1.0, "mu_scale": 0.5, "norm_offset_scale": 0.05}
CAST = reference_train.identity_cast
MODEL = dict(
    vocab_size=VOCAB, hidden_size=32,
    layers=[{"attention": "eva", "mlp": "dense", "repeat": 2}],
    attention_kinds={"eva": {"window": W, "rope": {"theta": 100000.0},
                             "eva": {"chunk_size": C}}},
    num_heads=4, num_kv_heads=4, head_dim=8, num_experts=0, top_k=0,
    moe_intermediate_size=0, intermediate_size=48, rms_norm_eps=1e-5,
    norm_unit_offset=True, num_pred_heads=8, remat_blocks=True,
)
OPTIMIZER = {"grad_clip": 40.0, "learning_rate": 0.0006, "decay": 0.99,
             "eps": 0.01}
# T + 1 at one, two and five windows, with the boundaries of each
SIZES = {"one_window": (8, (3,)), "two_windows": (16, (5,)),
         "five_windows": (40, (13, 27))}


def tiny(**over):
    model = dict(MODEL, **over)
    return decoder_lm(**model), model


def inputs(net, model, seed, steps, done_at):
    """``steps`` = T + 1 positions, a boundary at each of ``done_at`` in
    every column."""
    params = seeded_eva.make_params(
        seeded_eva.param_shapes(net), seed, model, SEEDING)
    config = {"num_actions": VOCAB,
              "observation": {"vocab": VOCAB, "zipf_s": 1.0}}
    batch = seeded_lm.make_learn_batch(seed, config, steps - 1, B, 0.0)
    done = np.zeros((steps, B), bool)
    for t in done_at:
        done[t, :] = True
    return params, dict(batch, done=jnp.asarray(done))


def close(a, b, tol=2e-4):
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol)


def program_forward(net, params, batch):
    with jax.default_matmul_precision("highest"):
        return learn_apply(net)(params, batch["obs"], batch["done"], ())


@pytest.mark.parametrize("size", list(SIZES))
def test_logits_and_baseline_match_the_reference(size):
    net, model = tiny()
    params, batch = inputs(net, model, 7, *SIZES[size])
    (logits, baseline), _, aux = program_forward(net, params, batch)
    with jax.default_matmul_precision("highest"):
        want_logits, want_baseline, _ = evabyte_tiny.forward(
            params, batch["obs"], batch["done"], (), CAST)
    assert logits.shape == (SIZES[size][0], B, VOCAB)
    close(logits, want_logits)
    close(baseline, want_baseline)
    assert not [k for k in aux if k.startswith("moe_")]


@pytest.mark.parametrize("size", list(SIZES))
def test_loss_and_every_gradient_leaf_match_the_reference(size):
    net, model = tiny()
    params, batch = inputs(net, model, 11, *SIZES[size])
    with jax.default_matmul_precision("highest"):
        (loss, metrics), grads = jax.value_and_grad(
            impala_loss, has_aux=True
        )(params, learn_apply(net), batch, ImpalaConfig(**LOSS))
        (want, parts), want_grads = jax.value_and_grad(
            evabyte_tiny.loss_fn, has_aux=True
        )(params, batch, LOSS, CAST)
    close(loss, want, 1e-5)
    close(metrics["mtp_loss"], parts["mtp_loss"], 1e-5)
    assert float(metrics["mtp_positions"]) == float(parts["mtp_positions"])
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-6
        assert float(jnp.max(jnp.abs(g - w))) <= 2e-4 * scale, (
            jax.tree_util.keystr(path))
        # one window reads no summary: phi and mu take no part there
        if size != "one_window" or not jax.tree_util.keystr(path).endswith(
                ("['phi']", "['mu']")):
            assert float(jnp.max(jnp.abs(w))) > 0, jax.tree_util.keystr(path)


@pytest.mark.parametrize("size", list(SIZES))
def test_three_rmsprop_steps_match_the_reference(size):
    """The step the benchmark times, through its first three updates,
    against the reference's loss, clip and RMSProp: the four numbers the
    cell's ``correct`` is decided by."""
    net, model = tiny()
    params, batch = inputs(net, model, 13, *SIZES[size])
    config = {"optimizer": OPTIMIZER, "loss": LOSS}
    optimizer = program.build_optimizer(config)
    step = make_impala_train_step(
        learn_apply(net), optimizer, ImpalaConfig(**LOSS), mesh=None,
        donate=False,
    )
    with jax.default_matmul_precision("highest"):
        _, first = reference_latent.program_first_steps(
            step, make_train_state(params, optimizer), batch, 3,
            OPTIMIZER["decay"],
        )
    follower = reference_latent.Follower(evabyte_tiny.loss_fn, config)
    reference = follower.follow(
        lambda: jax.tree_util.tree_map(jnp.copy, params), batch, 3,
        against=first["grad_abs"])
    numbers = reference_latent.numbers(first, reference)
    assert max(numbers.values()) < 1e-4, numbers
    close(first["mtp_losses"], reference["mtp_losses"], 1e-5)


@pytest.mark.parametrize("boundary", [
    pytest.param(21, id="inside_a_chunk"),
    pytest.param(22, id="on_a_chunks_edge"),
    pytest.param(24, id="on_a_windows_edge"),
])
def test_a_pack_of_two_episodes_equals_the_two_run_apart(boundary):
    """Positions are not reset at a boundary, so an episode run apart is
    run at its own positions with other bytes in the other episode's
    place: what the second episode's positions give does not depend on a
    byte of the first (its chunks, the one that straddles the boundary
    too, are read for the second episode's part alone), nor the first's
    on the second's."""
    net, model = tiny()
    steps = 40
    params, batch = inputs(net, model, 17, steps, (boundary,))
    other = jax.random.randint(jax.random.PRNGKey(3), (steps, B), 0, VOCAB)
    first = (jnp.arange(steps) < boundary)[:, None]
    (packed, packed_v), _, _ = program_forward(net, params, batch)
    (late, late_v), _, _ = program_forward(
        net, params, dict(batch, obs=jnp.where(first, other, batch["obs"])))
    (early, early_v), _, _ = program_forward(
        net, params, dict(batch, obs=jnp.where(first, batch["obs"], other)))
    assert float(jnp.max(jnp.abs(other - batch["obs"]))) > 0
    close(packed[boundary:], late[boundary:], 1e-5)
    close(packed_v[boundary:], late_v[boundary:], 1e-5)
    close(packed[:boundary], early[:boundary], 1e-5)
    close(packed_v[:boundary], early_v[:boundary], 1e-5)
    # and without the boundary the second episode does read the first
    whole = dict(batch, done=jnp.zeros_like(batch["done"]))
    (a, _), _, _ = program_forward(net, params, whole)
    (b, _), _, _ = program_forward(
        net, params, dict(whole, obs=jnp.where(first, other, batch["obs"])))
    assert float(jnp.max(jnp.abs(a[-1] - b[-1]))) > 1e-3


@pytest.mark.parametrize("done_at", [(), (13, 27)],
                         ids=["one_episode", "three_episodes"])
def test_chunks_of_one_and_no_mu_is_full_causal_attention(done_at):
    """A chunk of one position pools itself (its softmax is over one
    score, whatever ``phi``), so with ``mu`` = 0 every earlier window is
    read exactly and the two key sets under one softmax are the causal
    triangle: the layer equals the grouped-head layer on
    ``dense_attention``, code that knows nothing of two sets."""
    kinds = {"eva": {"window": W, "rope": {"theta": 100000.0},
                     "eva": {"chunk_size": 1}}}
    net, model = tiny(attention_kinds=kinds)
    params, batch = inputs(net, model, 19, 40, done_at)
    plain, _ = tiny(attention_kinds={"eva": {
        "window": None, "rope": {"theta": 100000.0}}},
        attention_backend="dense")

    def without(tree, drop):
        return {k: without(v, drop) if isinstance(v, dict) else v
                for k, v in tree.items() if k not in drop}

    zero_mu = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x)
        if jax.tree_util.keystr(path).endswith("['mu']") else x, params)
    (got, got_v), _, _ = program_forward(net, zero_mu, batch)
    (want, want_v), _, _ = program_forward(
        plain, without(params, ("phi", "mu")), batch)
    close(got, want, 2e-5)
    close(got_v, want_v, 2e-5)
    (moved, _), _, _ = program_forward(net, params, batch)  # mu as seeded
    assert float(jnp.max(jnp.abs(moved - want))) > 1e-3


def _two_sets(T=512, heads=2, D=16, window=128, chunk=4, done_at=()):
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    q, k, v = (jax.random.normal(ks[i], (1, heads, T, D)) for i in range(3))
    phi, mu = (jax.random.normal(ks[i], (heads, D)) for i in (3, 4))
    done = np.zeros((1, T), bool)
    done[0, list(done_at)] = True
    seg = jnp.cumsum(jnp.asarray(done), axis=1).astype(jnp.int32)
    weights = jax.random.normal(ks[5], (1, heads, T, D))
    lse_weights = jax.random.normal(ks[6], (1, heads, T))
    return (q, k, v, phi, mu), eva_ids(seg, T, window, chunk), (
        weights, lse_weights)


def _both_calls(backend, tensors, ids, weights, **kw):
    ids_q, ids_k, own, bits = ids

    def f(q, k, v, phi, mu):
        kt, vt = eva_summaries(k, v, own, phi, mu)
        local = attn_ops.attention(
            q, k, v, backend=backend, causal=True, segment_ids=ids_q,
            return_lse=True, **kw)
        earlier = attn_ops.attention(
            q, kt, vt, backend=backend, causal=False, segment_ids=ids_q,
            kv_segment_ids=ids_k, rank_bits=bits, return_lse=True, **kw)
        merged = merge_attention(*local, *earlier)
        # every output weighed, the row statistics too: their cotangent
        # enters both backward kernels
        total = jnp.sum(merged * weights[0]) + sum(
            jnp.sum(o * weights[0]) + jnp.sum(
                jnp.where(lse > -1e29, lse, 0.0) * weights[1])
            for o, lse in (local, earlier))
        return total, (merged, *local, *earlier)

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            f, argnums=(0, 1, 2, 3, 4), has_aux=True)(*tensors)


@pytest.mark.parametrize("backend,kw", [
    ("flash", dict(block_q=128, block_k=128, interpret=True)),
    ("blockwise", dict(block_k=64)),
])
@pytest.mark.parametrize("done_at", [(), (70, 258, 300)],
                         ids=["one_episode", "four_episodes"])
def test_both_calls_and_their_merge_match_plain_attention(backend, kw,
                                                          done_at):
    tensors, ids, weights = _two_sets(done_at=done_at)
    (want, want_out), want_grads = _both_calls("dense", tensors, ids, weights)
    (got, got_out), got_grads = _both_calls(
        backend, tensors, ids, weights, **kw)
    close(got, want, 1e-5)
    for a, b in zip(got_out, want_out):
        close(a, b, 2e-5)
    for a, b in zip(got_grads, want_grads):
        close(a, b, 5e-5)
    # a query of the first window reads no summary: zeros, the empty
    # set's statistic, and the merge is the local result
    merged, local, _, earlier, earlier_lse = got_out
    assert not np.any(np.asarray(earlier[:, :, :128]))
    assert float(jnp.max(earlier_lse[:, :, :128])) == np.float32(-1e30)
    close(merged[:, :, :128], local[:, :, :128], 1e-6)


def test_the_merge_is_one_softmax_over_both_key_sets():
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    q = jax.random.normal(ks[0], (1, 2, 16, 8))
    k1, v1, k2, v2 = (
        jax.random.normal(ks[i], (1, 2, n, 8))
        for i, n in ((1, 16), (2, 16), (3, 5), (4, 5)))
    a = dense_attention(q, k1, v1, causal=True, return_lse=True)
    b = dense_attention(q, k2, v2, return_lse=True)
    scores = jnp.concatenate([
        jnp.where(jnp.tril(jnp.ones((16, 16), bool)),
                  jnp.einsum("bhqd,bhkd->bhqk", q, k1), -jnp.inf),
        jnp.einsum("bhqd,bhkd->bhqk", q, k2)], axis=-1) / np.sqrt(8)
    want = jnp.einsum(
        "bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1),
        jnp.concatenate([v1, v2], axis=2))
    close(merge_attention(*a, *b), want, 1e-5)


def test_ranks_need_two_id_arrays_and_no_causal_mask():
    q = jnp.zeros((1, 1, 8, 4))
    seg = jnp.zeros((1, 8), jnp.int32)
    for kw in (dict(segment_ids=seg), dict(segment_ids=seg,
               kv_segment_ids=seg, causal=True)):
        with pytest.raises(ValueError, match="rank_bits"):
            dense_attention(q, q, q, rank_bits=2, **kw)


@pytest.mark.parametrize("what,digest", [
    ("forward", "e4242b21c52596c0"), ("gradient", "f0a776201a8c78e9")])
def test_the_flash_call_without_lse_traces_the_jaxpr_it_traced_before(
        what, digest):
    """Grouped heads, a window, a score scale and a narrower value head: a
    call that asks for no ``lse`` and no ranks traces the forward program
    of the commit before the statistics became an output (8e3f08f, read
    there), and with its gradient the program of the commit that made the
    backward one kernel (PR 47, read there); whoever changes either kernel
    reads the new text here."""
    q = jnp.zeros((1, 4, 256, 16))
    k = jnp.zeros((1, 2, 256, 16))
    v = jnp.zeros((1, 2, 256, 8))
    seg = jnp.zeros((1, 256), jnp.int32)

    def f(q, k, v):
        return flash_attention(
            q, k, v, causal=True, segment_ids=seg, window=96, block_q=128,
            block_k=128, interpret=True, scale=0.3).sum()

    fn = f if what == "forward" else jax.value_and_grad(f, argnums=(0, 1, 2))
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(fn)(q, k, v)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


@pytest.mark.parametrize("remat,keeps", [
    (False, None), (True, True), ("cores", True), ("input", False),
], ids=["nothing_rebuilt", "true_is_cores", "cores", "input"])
def test_what_a_rebuilt_block_keeps_is_one_field(monkeypatch, remat, keeps):
    """``remat_blocks`` is the stack's one decision of what a block keeps:
    false, everything; ``"cores"`` (what true has always meant), its input
    and its two attention cores' outputs and statistics, so that every
    flash call of the trace counts as kept; ``"input"``, its input alone,
    and none does. Loss and gradients are the same whichever it is."""
    import functools

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
                          attention_block=4)
        params, batch = inputs(net, model, 7, *SIZES["five_windows"])
        kept, calls = counts()
        (loss, _), grads = jax.value_and_grad(impala_loss, has_aux=True)(
            params, learn_apply(net), batch, ImpalaConfig(**LOSS)
        )
        after = counts()
        return loss, grads, after[0] - kept, after[1] - calls

    loss, grads, kept, calls = loss_and_grads(remat)
    assert calls >= 2  # both calls of the scanned block
    assert kept == (calls if keeps else 0)
    want_loss, want_grads, _, _ = loss_and_grads(False)
    close(loss, want_loss, 1e-6)
    for g, w in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        scale = max(float(jnp.max(jnp.abs(w))), 1e-12)
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-5 * scale


def test_an_unknown_remat_blocks_is_refused():
    net, model = tiny(remat_blocks="attention")
    with pytest.raises(ValueError, match="remat_blocks"):
        inputs(net, model, 7, *SIZES["one_window"])


def test_the_eight_heads_are_asked_for_the_next_eight_bytes():
    """Head 0's columns are the policy's logits; head i's cross-entropy
    is against byte t + 1 + i where that byte is of t's episode, and
    ``mtp_positions`` counts those, by hand."""
    net, model = tiny()
    steps, boundary = 24, 10
    params, batch = inputs(net, model, 23, steps, (boundary,))
    (logits, _), _, aux = program_forward(net, params, batch)
    bare, _ = tiny(num_pred_heads=1)
    hidden_params = jax.tree_util.tree_map(lambda x: x, params)
    kernel = params["params"]["head"]["kernel"]
    total = count = 0.0
    obs = np.asarray(batch["obs"])
    for i in range(8):
        hidden_params["params"]["head"] = {
            "kernel": kernel[:, i * VOCAB:(i + 1) * VOCAB]}
        (head_i, _), _, _ = program_forward(bare, hidden_params, batch)
        if i == 0:
            close(logits, head_i, 1e-5)
            continue
        logp = np.asarray(jax.nn.log_softmax(head_i, axis=-1))
        for t in range(steps):
            ahead = t + 1 + i
            if ahead < steps and (t < boundary) == (ahead < boundary):
                for b in range(B):
                    total -= logp[t, b, obs[ahead, b]]
                    count += 1
    by_hand = sum(
        max(0, n - 1 - i) for n in (boundary, steps - boundary)
        for i in range(1, 8)) * B
    assert count == by_hand == float(aux["mtp_positions"])
    close(aux["mtp_loss"], total / count, 1e-5)


def test_the_unit_offset_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 16)) * 3.0
    g = jax.random.normal(jax.random.PRNGKey(1), (16,)) * 0.1
    norm = RMSNorm(1e-5, jnp.float32, True)
    assert not np.any(np.asarray(
        norm.init(jax.random.PRNGKey(0), x)["params"]["scale"]))
    want = x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-5)
    close(norm.apply({"params": {"scale": g}}, x), want * (1 + g), 1e-6)
    close(norm.apply({"params": {"scale": jnp.zeros(16)}}, x), want, 1e-6)
    plain = RMSNorm(1e-5, jnp.float32)
    close(plain.apply({"params": {"scale": g}}, x), want * g, 1e-6)
    assert np.all(np.asarray(
        plain.init(jax.random.PRNGKey(0), x)["params"]["scale"]) == 1)


@pytest.mark.parametrize("done_at", [(), (5, 6, 21)],
                         ids=["one_episode", "boundaries"])
def test_the_steps_counters_are_the_pairs_a_mask_shows(done_at):
    steps = 40
    done = np.zeros((steps, 1), bool)
    done[list(done_at)] = True
    seg = np.cumsum(done[:, 0])
    t, s = np.arange(steps)[:, None], np.arange(steps)[None, :]
    local = (s <= t) & (s // W == t // W) & (seg[s] == seg[t])
    j = np.arange(steps // C)[None, :]
    earlier = (j * C // W < t // W) & (seg[j * C + C - 1] == seg[t])
    cut = sum(seg[c * C] != seg[c * C + C - 1] for c in range(steps // C))
    counts = eva_pair_counts(jnp.asarray(seg)[None], steps, W, C)
    assert {k: int(v) for k, v in counts.items()} == {
        "eva_local_pairs": local.sum(), "eva_summary_pairs": earlier.sum(),
        "eva_chunks_cut": cut}
    # the step's metrics: every block's, and no expert layer's counter
    net, model = tiny()
    params, batch = inputs(net, model, 29, steps, done_at)
    batch = jax.tree_util.tree_map(lambda x: x[:, :1], batch)
    _, metrics = impala_loss(
        params, learn_apply(net), batch, ImpalaConfig(**LOSS))
    assert int(metrics["eva_local_pairs"]) == 2 * local.sum()
    assert int(metrics["eva_summary_pairs"]) == 2 * earlier.sum()
    assert int(metrics["eva_chunks_cut"]) == 2 * cut
    assert "mtp_loss" in metrics and "moe_overflow" not in metrics
    mine = counts_eva.attention_counts(model | {"attention_block": 4},
                                       done[:, 0])
    assert (mine["local_pairs"], mine["summary_pairs"],
            mine["chunks_cut"]) == (local.sum(), earlier.sum(), cut)


def test_a_last_chunk_cut_short_is_read_by_no_one():
    """T + 1 = 37: the last chunk has one position. It lies in the last
    window, whose summaries no query reads; the program agrees with the
    reference there too."""
    net, model = tiny()
    params, batch = inputs(net, model, 31, 37, (20,))
    (logits, _), _, _ = program_forward(net, params, batch)
    forward = evabyte_pp8.make_forward(
        dict(evabyte_tiny.TINY, query_rows=37, head_rows=37, mlp_rows=37))
    with jax.default_matmul_precision("highest"):
        want, _, _ = forward(params, batch["obs"], batch["done"], (), CAST)
    close(logits, want)


def test_the_benchmarks_configuration_is_the_published_model():
    with open(os.path.join(
            REPO, "benchmark", "configs", "evabyte_pp8.json")) as f:
        config = json.load(f)
    net = program.build_model(config)
    shapes = seeded_eva.param_shapes(net)

    def size(tree):
        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(tree))

    p = shapes["params"]
    assert size(shapes) == 821_370_881
    block = p["block_0"]
    assert size(block) == 4 * 202_391_552
    assert size(block["attn"]) == 4 * (67_108_864 + 8_192)
    assert size(block["mlp"]) == 4 * 135_266_304
    assert size(block["norm1"]) + size(block["norm2"]) == 4 * 8_192
    assert size(p["embed"]) == 1_310_720 and size(p["head"]) == 10_485_760
    assert size(p["final_norm"]) + size(p["baseline"]) == 8_193
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 32}
    assert config["num_hidden_layers"] == 4
    kw = config["model"]["kwargs"]
    kind = kw["attention_kinds"]["eva"]
    assert (kw["hidden_size"], kw["num_heads"], kw["num_kv_heads"],
            kw["intermediate_size"], kw["vocab_size"], kw["num_pred_heads"],
            kind["window"], kind["eva"]["chunk_size"], kind["rope"]["theta"],
            kw["rms_norm_eps"], kw["norm_unit_offset"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], config["intermediate_size"],
        config["vocab_size"], config["num_pred_heads"],
        config["window_size"], config["chunk_size"], config["rope_theta"],
        config["rms_norm_eps"], config["norm_add_unit_offset"])
    assert kw["head_dim"] * kw["num_heads"] == kw["hidden_size"]
    assert kw["layers"] == [
        {"attention": "eva", "mlp": "dense", "repeat": 4}]
    for key in ("source", "source_detail", "deployment", "assumed"):
        assert config[key], key
    assumed = " ".join(config["assumed"])
    for item in ("d^-1/2", "mu", "after the rotary", "fixed blocks",
                 "episode", "head-major", "value unit", "fp32_skip_add",
                 "mtp_cost", "RMSProp", "not reset", "phi_scale"):
        assert item in assumed, item
