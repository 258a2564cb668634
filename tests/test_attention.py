"""Attention stack tests: dense oracle vs blockwise vs pallas flash
(interpret mode) vs ring attention on the 8-device virtual mesh, plus the
TransformerNet agent model.

The reference has no attention machinery (SURVEY.md §5) — the oracle here is
dense softmax attention, property-tested the way the reference tests its
Batcher against torch.stack/cat (test/unit/test_batcher.py:14-53).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from moolib_tpu.ops.attention import (
    KEEP_CORES,
    attention,
    blockwise_attention,
    dense_attention,
    flash_attention,
    keeping_cores,
)
from moolib_tpu.ops.ring_attention import (
    ring_attention,
    sequence_sharded_attention,
)
from moolib_tpu.parallel.mesh import make_mesh


def _qkv(rng, B=2, H=3, T=64, D=16, dtype=np.float32):
    return tuple(
        jnp.asarray(rng.standard_normal((B, H, T, D)), dtype)
        for _ in range(3)
    )


def _segs(rng, B=2, T=64):
    return jnp.asarray(
        np.cumsum(rng.random((B, T)) < 0.08, axis=1), jnp.int32
    )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("with_segs", [False, True])
def test_blockwise_matches_dense(rng, causal, with_segs):
    q, k, v = _qkv(rng)
    seg = _segs(rng) if with_segs else None
    o1 = dense_attention(q, k, v, causal=causal, segment_ids=seg)
    o2 = blockwise_attention(
        q, k, v, causal=causal, segment_ids=seg, block_k=16
    )
    np.testing.assert_allclose(o1, o2, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("with_segs", [False, True])
def test_flash_matches_dense(rng, causal, with_segs):
    q, k, v = _qkv(rng)
    seg = _segs(rng) if with_segs else None
    o1 = dense_attention(q, k, v, causal=causal, segment_ids=seg)
    o3 = flash_attention(
        q, k, v, causal=causal, segment_ids=seg, block_q=16, block_k=16,
        interpret=True,
    )
    np.testing.assert_allclose(o1, o3, atol=2e-5)


def test_blockwise_ragged_tail(rng):
    """Tk not a multiple of block_k: padded keys must not attend."""
    q, k, v = _qkv(rng, T=50)
    o1 = dense_attention(q, k, v, causal=True)
    o2 = blockwise_attention(q, k, v, causal=True, block_k=16)
    np.testing.assert_allclose(o1, o2, atol=2e-5)


def test_gradients_match(rng):
    q, k, v = _qkv(rng, T=32)
    seg = _segs(rng, T=32)

    def loss(fn, inputs, **kw):
        q, k, v = inputs
        return jnp.sum(fn(q, k, v, causal=True, segment_ids=seg, **kw) ** 2)

    g_dense = jax.grad(lambda i: loss(dense_attention, i))((q, k, v))
    g_block = jax.grad(lambda i: loss(blockwise_attention, i, block_k=16))(
        (q, k, v)
    )
    g_flash = jax.grad(
        lambda i: loss(flash_attention, i, block_q=16, block_k=16,
                       interpret=True)
    )((q, k, v))
    for a, b in zip(g_dense, g_block):
        np.testing.assert_allclose(a, b, atol=1e-4)
    for a, b in zip(g_dense, g_flash):
        np.testing.assert_allclose(a, b, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("with_segs", [False, True])
def test_ring_matches_dense(rng, causal, with_segs):
    mesh = make_mesh(dp=1, sp=8)
    q, k, v = _qkv(rng)
    seg = _segs(rng) if with_segs else None
    o1 = dense_attention(q, k, v, causal=causal, segment_ids=seg)
    o2 = sequence_sharded_attention(
        mesh, q, k, v, causal=causal, segment_ids=seg
    )
    np.testing.assert_allclose(o1, np.asarray(o2), atol=2e-5)


def test_ring_gradients(rng):
    mesh = make_mesh(dp=1, sp=8)
    q, k, v = _qkv(rng, T=32, B=1, H=2, D=8)
    spec = P(None, None, "sp", None)

    def ring_loss(q):
        f = jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, causal=True),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
        )
        return jnp.sum(f(q, k, v) ** 2)

    g1 = jax.grad(
        lambda q: jnp.sum(dense_attention(q, k, v, causal=True) ** 2)
    )(q)
    g2 = jax.jit(jax.grad(ring_loss))(q)
    np.testing.assert_allclose(g1, np.asarray(g2), atol=1e-4)


def test_attention_dispatcher(rng):
    q, k, v = _qkv(rng, T=16)
    o_auto = attention(q, k, v, causal=True)
    o_dense = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(o_auto, o_dense, atol=2e-5)
    with pytest.raises(ValueError):
        attention(q, k, v, backend="nope")


# -- TransformerNet agent ---------------------------------------------------


def _net_and_params(rng_key, backend="dense", T=12, B=3, F=5, A=4):
    from moolib_tpu.models import TransformerNet

    net = TransformerNet(
        num_actions=A, d_model=32, num_layers=2, num_heads=2,
        attention_backend=backend,
    )
    obs = jnp.asarray(
        np.random.default_rng(0).standard_normal((T, B, F)), jnp.float32
    )
    done = jnp.asarray(np.random.default_rng(1).random((T, B)) < 0.15)
    params = net.init(rng_key, obs, done, ())
    return net, params, obs, done


def test_transformer_forward_shapes():
    net, params, obs, done = _net_and_params(jax.random.PRNGKey(0))
    (logits, baseline), state = net.apply(params, obs, done, ())
    assert logits.shape == (12, 3, 4) and baseline.shape == (12, 3)
    assert state == ()


def test_transformer_backends_agree():
    net_d, params, obs, done = _net_and_params(
        jax.random.PRNGKey(0), backend="dense"
    )
    from jax.experimental.pallas import tpu as pltpu

    from moolib_tpu.models import TransformerNet

    for backend in ("blockwise", "flash"):
        net_b = TransformerNet(
            num_actions=4, d_model=32, num_layers=2, num_heads=2,
            attention_backend=backend,
        )
        (l1, b1), _ = net_d.apply(params, obs, done, ())
        # The model has no interpret switch (a chip must never interpret):
        # on CPU the flash backend runs under jax's own interpret context.
        with pltpu.force_tpu_interpret_mode():
            (l2, b2), _ = net_b.apply(params, obs, done, ())
        np.testing.assert_allclose(l1, l2, atol=2e-4)
        np.testing.assert_allclose(b1, b2, atol=2e-4)


def test_transformer_respects_episode_boundaries():
    """A query after a reset must not see pre-reset frames: changing frames
    before the reset must not change post-reset outputs."""
    net, params, obs, done = _net_and_params(jax.random.PRNGKey(0))
    T, B = obs.shape[:2]
    done = jnp.zeros((T, B), bool).at[6, 0].set(True)
    (l1, _), _ = net.apply(params, obs, done, ())
    obs2 = obs.at[:6, 0].add(10.0)  # pre-reset frames of lane 0
    (l2, _), _ = net.apply(params, obs2, done, ())
    np.testing.assert_allclose(l1[6:, 0], l2[6:, 0], atol=1e-5)
    # sanity: pre-reset outputs DID change
    assert float(jnp.max(jnp.abs(l1[:6, 0] - l2[:6, 0]))) > 1e-3


def test_transformer_in_impala_learner():
    """TransformerNet plugs into the IMPALA train step on a dp mesh."""
    import optax

    from moolib_tpu.learner import (
        ImpalaConfig,
        make_impala_train_step,
        make_train_state,
        replicate_state,
    )
    from moolib_tpu.parallel.mesh import shard_batch

    net, params, obs, done = _net_and_params(
        jax.random.PRNGKey(0), T=5, B=8
    )
    mesh = make_mesh(dp=8)
    rng = np.random.default_rng(0)
    T, B, A = 4, 8, 4
    batch = {
        "obs": jnp.asarray(
            rng.standard_normal((T + 1, B, 5)), jnp.float32
        ),
        "done": jnp.asarray(rng.random((T + 1, B)) < 0.1),
        "rewards": jnp.asarray(rng.standard_normal((T + 1, B)), jnp.float32),
        "actions": jnp.asarray(rng.integers(0, A, (T, B)), jnp.int32),
        "behavior_logits": jnp.zeros((T, B, A), jnp.float32),
        "core_state": (),
    }
    opt = optax.adam(1e-3)
    state = replicate_state(make_train_state(params, opt), mesh)
    step = make_impala_train_step(
        net.apply, opt, ImpalaConfig(), mesh=mesh, donate=False
    )
    state, metrics = step(state, shard_batch(mesh, batch))
    assert np.isfinite(float(metrics["total_loss"]))
    assert int(state.step) == 1


class TestZigzag:
    """Zigzag (striped) causal ring attention vs the dense oracle."""

    def _mesh(self, n):
        from moolib_tpu.parallel.mesh import make_mesh

        return make_mesh(dp=1, sp=n, devices=jax.devices()[:n])

    def test_zigzag_order_roundtrip(self):
        from moolib_tpu.ops.ring_attention import zigzag_order

        perm = zigzag_order(4, 32)
        assert sorted(perm.tolist()) == list(range(32))
        inv = np.argsort(perm)
        x = np.arange(32)
        np.testing.assert_array_equal(x[perm][inv], x)

    @pytest.mark.parametrize("n", [2, 4, 8])
    def test_matches_dense_causal(self, n, rng):
        from moolib_tpu.ops.attention import dense_attention
        from moolib_tpu.ops.ring_attention import zigzag_sharded_attention

        B, H, S, D = 2, 2, 4 * n, 8
        q, k, v = (
            jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
            for _ in range(3)
        )
        ref = dense_attention(q, k, v, causal=True)
        out = zigzag_sharded_attention(self._mesh(n), q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_matches_dense_causal_with_segments(self, rng):
        from moolib_tpu.ops.attention import dense_attention
        from moolib_tpu.ops.ring_attention import zigzag_sharded_attention

        n, B, H, S, D = 4, 2, 2, 32, 8
        q, k, v = (
            jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
            for _ in range(3)
        )
        seg = jnp.asarray(
            np.cumsum(rng.random((B, S)) < 0.15, axis=-1), jnp.int32
        )
        ref = dense_attention(q, k, v, causal=True, segment_ids=seg)
        out = zigzag_sharded_attention(self._mesh(n), q, k, v,
                                       segment_ids=seg)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_gradients_match_dense(self, rng):
        from moolib_tpu.ops.attention import dense_attention
        from moolib_tpu.ops.ring_attention import zigzag_sharded_attention

        n, B, H, S, D = 2, 1, 2, 16, 4
        q, k, v = (
            jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
            for _ in range(3)
        )
        mesh = self._mesh(n)

        def loss_ref(q, k, v):
            return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

        def loss_zig(q, k, v):
            return jnp.sum(zigzag_sharded_attention(mesh, q, k, v) ** 2)

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        g_zig = jax.grad(loss_zig, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_zig):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-5, atol=5e-5
            )


def test_transformer_zigzag_backend_matches_dense():
    """TransformerNet(attention_backend='zigzag') under shard_map on
    zigzag-permuted inputs reproduces the dense model on the original
    layout — the balanced long-context configuration end to end."""
    from moolib_tpu.models import TransformerNet
    from moolib_tpu.models.transformer import segment_ids_from_done
    from moolib_tpu.ops.ring_attention import zigzag_order

    n = 4
    mesh = make_mesh(dp=1, sp=n, devices=jax.devices()[:n])
    T, B, F, A = 8 * n, 2, 5, 3
    rng = np.random.default_rng(0)
    obs = jnp.asarray(rng.standard_normal((T, B, F)), jnp.float32)
    done = jnp.asarray(rng.random((T, B)) < 0.1)
    seg = segment_ids_from_done(done)  # [B, T]
    positions = jnp.arange(T)
    kw = dict(num_actions=A, d_model=16, num_layers=1, num_heads=2)

    dense = TransformerNet(attention_backend="dense", **kw)
    params = dense.init(
        jax.random.PRNGKey(0), obs, done, (), segment_ids=seg,
        positions=positions,
    )
    (l_ref, b_ref), _ = dense.apply(
        params, obs, done, (), segment_ids=seg, positions=positions
    )

    zig = TransformerNet(attention_backend="zigzag", ring_axis="sp", **kw)
    perm = zigzag_order(n, T)
    inv = np.argsort(perm)
    obs_z, done_z = obs[perm], done[perm]
    seg_z, pos_z = seg[:, perm], positions[perm]

    def f(params, obs, done, seg, pos):
        (l, b), _ = zig.apply(
            params, obs, done, (), segment_ids=seg, positions=pos
        )
        return l, b

    l_z, b_z = jax.jit(
        jax.shard_map(
            f, mesh=mesh,
            in_specs=(P(), P("sp"), P("sp"), P(None, "sp"), P("sp")),
            out_specs=(P("sp"), P("sp")),
        )
    )(params, obs_z, done_z, seg_z, pos_z)

    np.testing.assert_allclose(
        np.asarray(l_z)[inv], np.asarray(l_ref), rtol=3e-5, atol=3e-5
    )
    np.testing.assert_allclose(
        np.asarray(b_z)[inv], np.asarray(b_ref), rtol=3e-5, atol=3e-5
    )


def test_transformer_zigzag_training_keeps_sharded_layout():
    """The documented long-context TRAINING path (VERDICT r3 weak #10):
    loss and gradients computed entirely in zigzag layout — per-shard
    partial losses psum'd inside shard_map, no inverse-permute / gather of
    the [T, ...] activations anywhere — must match the dense reference's
    gradients. T is large enough that a full gather per step would be the
    dominant memory traffic."""
    from moolib_tpu.models import TransformerNet
    from moolib_tpu.models.transformer import segment_ids_from_done
    from moolib_tpu.ops.ring_attention import zigzag_order

    n = 4
    mesh = make_mesh(dp=1, sp=n, devices=jax.devices()[:n])
    T, B, F, A = 512, 2, 5, 3
    rng_np = np.random.default_rng(1)
    obs = jnp.asarray(rng_np.standard_normal((T, B, F)), jnp.float32)
    done = jnp.asarray(rng_np.random((T, B)) < 0.05)
    seg = segment_ids_from_done(done)
    positions = jnp.arange(T)
    kw = dict(num_actions=A, d_model=16, num_layers=1, num_heads=2,
              max_len=T)

    dense = TransformerNet(attention_backend="dense", **kw)
    params = dense.init(
        jax.random.PRNGKey(0), obs, done, (), segment_ids=seg,
        positions=positions,
    )

    def ref_loss(params):
        (l, b), _ = dense.apply(
            params, obs, done, (), segment_ids=seg, positions=positions
        )
        return jnp.mean(l.astype(jnp.float32) ** 2) + jnp.mean(
            b.astype(jnp.float32) ** 2
        )

    g_ref = jax.jit(jax.grad(ref_loss))(params)

    zig = TransformerNet(attention_backend="zigzag", ring_axis="sp", **kw)
    perm = zigzag_order(n, T)
    obs_z, done_z = obs[perm], done[perm]
    seg_z, pos_z = seg[:, perm], positions[perm]

    def shard_loss(params, obs, done, seg, pos):
        (l, b), _ = zig.apply(
            params, obs, done, (), segment_ids=seg, positions=pos
        )
        # Per-shard partial sums; the ONLY cross-shard op is the scalar
        # psum — activations never regroup to the full sequence.
        s = jnp.sum(l.astype(jnp.float32) ** 2) + A * jnp.sum(
            b.astype(jnp.float32) ** 2
        )
        return jax.lax.psum(s, "sp") / (T * B * A)

    def zig_loss(params):
        return jax.shard_map(
            shard_loss, mesh=mesh,
            in_specs=(P(), P("sp"), P("sp"), P(None, "sp"), P("sp")),
            out_specs=P(),
        )(params, obs_z, done_z, seg_z, pos_z)

    g_zig = jax.jit(jax.grad(zig_loss))(params)
    # No [T, ...]-shaped gather in the compiled module: the only all-gather
    # allowed is parameter-sized (grad accumulation onto replicated params).
    hlo = jax.jit(jax.grad(zig_loss)).lower(params).compile().as_text()
    t_bytes = T * B * 16 * 4  # a full [T, B, d_model] f32 gather
    import math as _math
    import re as _re

    for m in _re.finditer(r"all-gather[^\n]*", hlo):
        for shape in _re.findall(r"f32\[([\d,]+)\]", m.group(0)):
            elems = _math.prod(int(d) for d in shape.split(",") if d)
            assert elems * 4 < t_bytes, m.group(0)[:120]

    for (pa, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(g_ref),
        jax.tree_util.tree_leaves_with_path(g_zig),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=8e-5, atol=8e-5,
            err_msg=str(pa),
        )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_kernel_with_segments(rng, causal):
    """The pallas backward (one kernel, P rebuilt from the saved lse)
    must match oracle gradients under segment masking, including
    fully-masked rows (unmatchable q segment => zero gradient, not NaN).
    Reference is blockwise_attention: like flash it returns zeros for
    fully-masked rows, where the finite-bias dense oracle degenerates to
    uniform attention."""
    B, H, T, D = 2, 2, 64, 16
    q, k, v = _qkv(rng, B=B, H=H, T=T, D=D)
    seg = _segs(rng, B=B, T=T)
    # Lane 0's first rows get a segment no key has: fully masked.
    seg_q = seg.at[0, :4].set(999)

    def ref_loss(q, k, v):
        return jnp.sum(
            blockwise_attention(q, k, v, causal=causal, segment_ids=seg_q,
                                kv_segment_ids=seg, block_k=16) ** 2
        )

    def flash_loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=causal, segment_ids=seg_q,
                            kv_segment_ids=seg, block_q=16,
                            block_k=16, interpret=True) ** 2
        )

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        assert np.isfinite(np.asarray(b)).all()
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        )
    # The fully-masked rows' q gradients are exactly zero.
    np.testing.assert_array_equal(np.asarray(g_fl[0])[0, :, :4, :], 0.0)


def _rank_ids(Tq, Tk, bits=4):
    """Ids as ``group << bits | rank`` that never decrease along either
    axis, in group or in rank: a query's rank is its window of 8, a key
    summarises a quarter of one, and the second episode starts at 5/8."""
    qpos, kpos = np.arange(Tq), np.arange(Tk) * Tq // Tk
    ids = [((pos >= Tq * 5 // 8) << bits) | (pos // 8)
           for pos in (qpos, kpos)]
    return tuple(jnp.asarray(i[None], jnp.int32) for i in ids) + (bits,)


@pytest.mark.parametrize("with_lse", [False, True],
                         ids=["out", "out_and_lse"])
@pytest.mark.parametrize("H,Hkv,D,Dv,Tk,kw", [
    (2, 2, 16, 16, 64, dict(causal=True)),
    (2, 2, 16, 16, 64, dict(causal=False, segs=True)),
    (2, 2, 16, 16, 32, dict(causal=False)),
    (4, 1, 16, 16, 64, dict(causal=True, segs=True)),
    (4, 1, 16, 16, 64, dict(causal=True, window=24, segs=True)),
    (2, 2, 16, 16, 64, dict(causal=True, window=40)),
    (2, 2, 16, 16, 32, dict(causal=False, ranks=True)),
    (8, 2, 16, 16, 32, dict(causal=False, ranks=True)),
    (2, 2, 192, 128, 64, dict(causal=True, segs=True)),
    (4, 1, 192, 128, 64, dict(causal=True, window=24, segs=True)),
], ids=["causal", "segments", "short_keys", "grouped", "grouped_window",
        "window", "ranks", "grouped_ranks", "wide_keys",
        "wide_keys_grouped_window"])
def test_flash_backward_matches_the_oracles_gradient(rng, H, Hkv, D, Dv, Tk,
                                                     kw, with_lse):
    """The one backward kernel against ``jax.grad`` of ``dense_attention``:
    every geometry that reaches it (the diagonal, a window's walk, episodes,
    ids as group and rank, ``G`` query heads on a key/value head whose dq it
    holds together, keys wider than values, fewer keys than queries), with
    and without a cotangent on the row statistics."""
    B, Tq = 1, 64
    q = jnp.asarray(rng.standard_normal((B, H, Tq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Hkv, Tk, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Hkv, Tk, Dv)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((B, H, Tq, Dv)), jnp.float32)
    w_lse = jnp.asarray(rng.standard_normal((B, H, Tq)), jnp.float32)
    kw = dict(kw)
    if kw.pop("segs", False):
        kw["segment_ids"] = _segs(rng, B=B, T=Tq)
    if kw.pop("ranks", False):
        kw["segment_ids"], kw["kv_segment_ids"], kw["rank_bits"] = _rank_ids(
            Tq, Tk)

    def loss(fn, need_lse=True, **more):
        def f(q, k, v):
            if not need_lse:  # the custom_vjp without the statistics
                return jnp.sum(fn(q, k, v, **kw, **more) * w)
            out, lse = fn(q, k, v, return_lse=True, **kw, **more)
            if not with_lse:
                return jnp.sum(out * w)
            # a row that saw no key carries no statistic
            return jnp.sum(out * w) + jnp.sum(
                jnp.where(lse > -1e29, lse, 0.0) * w_lse)

        return jax.jit(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)

    # the oracle's rows that see no key are zeros only with the statistics
    want = loss(dense_attention)
    got = loss(flash_attention, need_lse=with_lse, block_q=16, block_k=16,
               interpret=True)
    for a, b, name in zip(want, got, "qkv"):
        assert a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=5e-5, err_msg=name
        )


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_flash_backward_spreads_grouped_heads_over_passes(rng, monkeypatch,
                                                          passes):
    """Four query heads on a key/value head whose dq does not fit the
    kernel's fast memory together: the backward walks them in two or four
    passes of its grid, each a partial dk/dv, and gives the gradient it
    gives in one. A single head that does not fit is refused by name."""
    from moolib_tpu.ops import attention as ops
    from moolib_tpu.telemetry import global_telemetry

    B, H, Hkv, T, D = 1, 8, 2, 64, 16
    q = jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((B, Hkv, T, D)), jnp.float32)
            for _ in range(2))
    seg = _segs(rng, B=B, T=T)
    one_head = ops._dq_resident_bytes(1, T, D, 4)
    monkeypatch.setattr(ops, "_DQ_VMEM_BUDGET", one_head * 4 // passes)
    assert ops._dq_passes(H // Hkv, T, D, 4) == passes

    def loss(fn, **kw):
        def f(q, k, v):
            return jnp.sum(fn(q, k, v, causal=True, window=24,
                              segment_ids=seg, **kw) ** 2)

        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    def traced():
        return global_telemetry().registry.value(
            "attention_backward_traced_total", form="fused") or 0

    before = traced()
    got = loss(flash_attention, block_q=16, block_k=16, interpret=True)
    assert traced() - before == 1  # one kernel whatever the passes
    for a, b in zip(loss(dense_attention), got):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=5e-5
        )
    monkeypatch.setattr(ops, "_DQ_VMEM_BUDGET", one_head - 1)
    with pytest.raises(ValueError, match="one query head's dq"):
        loss(flash_attention, block_q=16, block_k=16, interpret=True)


# -- what a rebuilt caller keeps of the flash kernels -----------------------


def _pallas_calls(fn, *args):
    """``pallas_call`` equations left in ``fn``'s jaxpr once what nothing
    reads is gone (the compiler drops the same): nested jaxprs are printed
    inside their equation, so the text holds every one."""
    from jax.interpreters import partial_eval as pe

    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    jaxpr, _ = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
    return str(jaxpr).count("pallas_call")


@pytest.mark.parametrize("backend,kernels", [
    # forward, the rebuilt forward, the backward; the policy drops the second
    ("flash", {"plain": 2, "rebuilt": 3, "kept": 2}),
    # no kernel and no named residual: the policy keeps nothing
    ("dense", {"plain": 0, "rebuilt": 0, "kept": 0}),
])
def test_a_rebuilt_caller_keeps_the_core_and_not_the_forward_kernel(
        rng, backend, kernels):
    """The gradient of a ``jax.checkpoint``-ed function around the flash
    call, with and without the policy that saves the core's named output
    and row statistics: one forward kernel fewer, the same bits."""
    from moolib_tpu.telemetry import global_telemetry

    q, k, v = _qkv(rng, T=32)
    seg = _segs(rng, T=32)
    w = jnp.asarray(rng.standard_normal((16, 16)) / 4, jnp.float32)
    kw = dict(block_q=16, block_k=16, interpret=True)

    def f(w, q, k, v):
        o = attention(
            q @ w, k @ w, v, backend=backend, causal=True, segment_ids=seg,
            **(kw if backend == "flash" else {})
        )
        return jnp.sum(jnp.tanh(o @ w) ** 2)

    forms = {
        "plain": f,
        "rebuilt": jax.checkpoint(f),
        "kept": jax.checkpoint(f, policy=KEEP_CORES),
    }
    results = {}
    for name, fn in forms.items():
        grad = jax.value_and_grad(fn, argnums=(0, 1, 2, 3))
        assert _pallas_calls(grad, w, q, k, v) == kernels[name], name
        results[name] = grad(w, q, k, v)  # op by op: no fusion differs
    for name in ("rebuilt", "kept"):
        for a, b in zip(jax.tree_util.tree_leaves(results["plain"]),
                        jax.tree_util.tree_leaves(results[name])):
            np.testing.assert_array_equal(a, b, err_msg=name)
    # the counter of calls whose core a rebuilt caller keeps: flash calls
    # traced inside keeping_cores(), and no others
    def kept():
        return global_telemetry().registry.value(
            "attention_cores_kept_total") or 0

    before = kept()
    f(w, q, k, v)
    assert kept() == before
    with keeping_cores():
        f(w, q, k, v)
    assert kept() - before == (backend == "flash")
    if backend == "dense":
        # and the two rebuilt programs are one program
        text = [
            [line for line in str(
                jax.make_jaxpr(jax.grad(forms[n]))(w, q, k, v)
            ).splitlines() if "policy=" not in line]
            for n in ("rebuilt", "kept")
        ]
        assert text[0] == text[1]
