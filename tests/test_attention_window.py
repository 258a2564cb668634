"""``attention(window=..., kv_heads < heads)``: the three backends agree,
forward and gradients, with a window shorter than the sequence, segments
that cut across windows, and fewer key/value heads than query heads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moolib_tpu.ops.attention import (
    _tiles,
    attention,
    blockwise_attention,
    dense_attention,
    flash_attention,
)


def _inputs(seed, B=2, H=4, Hkv=2, T=64, D=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, H, T, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, T, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, T, D), jnp.float32)
    seg = jnp.cumsum(jax.random.uniform(ks[3], (B, T)) < 0.08, axis=1)
    w = jax.random.normal(ks[4], (B, H, T, D), jnp.float32)
    return q, k, v, seg.astype(jnp.int32), w


def _oracle(q, k, v, seg, window):
    """Repeats materialised, mask written out: what no backend does."""
    B, H, T, D = q.shape
    G = H // k.shape[1]
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = (j <= i) & (seg[:, None, :, None] == seg[:, None, None, :])
    if window is not None:
        seen = seen & (i - j < window)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(seen, s, -1e30), -1), v
    )


BACKENDS = {
    "dense": lambda *a, **kw: dense_attention(*a, **kw),
    "blockwise": lambda *a, **kw: blockwise_attention(*a, block_k=16, **kw),
    "flash": lambda *a, **kw: flash_attention(
        *a, block_q=16, block_k=16, interpret=True, **kw
    ),
}


@pytest.mark.parametrize("backend", sorted(BACKENDS))
@pytest.mark.parametrize("window,Hkv", [(24, 2), (7, 1), (None, 1), (64, 4)])
def test_window_and_grouped_heads_match_the_oracle(backend, window, Hkv):
    q, k, v, seg, w = _inputs(0, Hkv=Hkv)
    fn = BACKENDS[backend]

    def loss(f):
        return lambda q, k, v: jnp.sum(w * f(q, k, v))

    ours = lambda q, k, v: fn(  # noqa: E731
        q, k, v, causal=True, segment_ids=seg, window=window
    )
    ref = lambda q, k, v: _oracle(q, k, v, seg, window)  # noqa: E731
    np.testing.assert_allclose(
        ours(q, k, v), ref(q, k, v), rtol=2e-5, atol=2e-5
    )
    g_ours = jax.grad(loss(ours), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ours, g_ref):
        assert a.shape == b.shape  # dk/dv keep the key/value heads
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_unequal_blocks_walk_only_what_the_window_reaches():
    """Block sizes that do not divide each other, a window that ends inside
    a block: the windowed walk still covers every visible tile."""
    q, k, v, seg, _ = _inputs(1, B=1, H=2, Hkv=1, T=96)
    out = flash_attention(
        q, k, v, causal=True, segment_ids=seg, window=20, block_q=32,
        block_k=16, interpret=True,
    )
    np.testing.assert_allclose(
        out, _oracle(q, k, v, seg, 20), rtol=2e-5, atol=2e-5
    )
    t = _tiles(q, k, True, 20, 32, 16)
    assert (t.n_q, t.n_k) == (3, 6)
    # a query block of 32 rows and 19 keys of reach span 4 blocks of 16,
    # not all 6; a key block is seen from at most 2 query blocks of 3
    assert (t.n_kw, t.n_qw) == (4, 2)


def test_window_needs_causal():
    q, k, v, _, _ = _inputs(2)
    for fn in (dense_attention, blockwise_attention, flash_attention):
        with pytest.raises(ValueError, match="causal"):
            fn(q, k, v, window=4)
    with pytest.raises(ValueError, match="split"):
        dense_attention(q[:, :3], k, v)


def test_the_dispatcher_passes_window_and_groups_through():
    q, k, v, seg, _ = _inputs(3)
    out = attention(q, k, v, backend="auto", causal=True, segment_ids=seg,
                    window=9)
    np.testing.assert_allclose(
        out, _oracle(q, k, v, seg, 9), rtol=2e-5, atol=2e-5
    )
