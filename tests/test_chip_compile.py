"""The kernels of the language-model cell, and the glyph embedding's backward
of the NetHack cell, compiled for the chip at the cells' real sizes, with no
chip attached: the TPU's compiler is installed here and compiles for a
described v5e. What interpret mode cannot show
(tiling, fast memory, a kernel Mosaic refuses) fails here at no chip time.
Nothing runs, so nothing here is a result or a time.

One file, and the topology is described inside a fixture: only the worker
that is handed this file loads the TPU's library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from moolib_tpu.ops.attention import flash_attention
from moolib_tpu.ops.embed import embed_lookup
from moolib_tpu.parallel import moe
from moolib_tpu.parallel.moe import moe_dropless

T, D, H, HKV, BLOCK = 8192, 128, 4, 1, 512  # mellum2_share8's share


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no compiler for it here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    and cannot be read back without a chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("window", [1024, None])
def test_flash_kernels_compile_at_the_cells_shape(one_chip, no_compile_cache,
                                                  window):
    def shape(heads, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((1, heads, T, D), dtype,
                                    sharding=one_chip)

    seg = jax.ShapeDtypeStruct((1, T), jnp.int32, sharding=one_chip)

    def step(q, k, v, seg):
        def loss(q, k, v):
            return flash_attention(
                q, k, v, causal=True, segment_ids=seg, window=window,
                block_q=BLOCK, block_k=BLOCK,
            ).astype(jnp.float32).sum()

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(step).lower(
        shape(H), shape(HKV), shape(HKV), seg
    ).compile()
    # forward, dQ and dK/dV, each a Mosaic kernel
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') >= 3


@pytest.mark.parametrize("grouped", ["gmm", "ragged_dot"])
def test_grouped_products_compile_at_the_cells_shape(one_chip,
                                                     no_compile_cache,
                                                     grouped, monkeypatch):
    # jax.default_backend() is the CPU here: say what the chip would run
    monkeypatch.setattr(moe, "resolve_grouped", lambda *a: grouped)
    d, f, E, held, rows = 2304, 896, 64, (0, 8), 20480

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {
        "router": s((d, E), jnp.float32),
        "w_gate": s((held[1], d, f), jnp.float32),
        "w_up": s((held[1], d, f), jnp.float32),
        "w_down": s((held[1], f, d), jnp.float32),
    }

    def step(params, x):
        def loss(params, x):
            y, _ = moe_dropless(params, x, top_k=8, held=held,
                                buffer_rows=rows)
            return y.astype(jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1))(params, x)

    compiled = jax.jit(step).lower(
        params, s((T, d), jnp.bfloat16)
    ).compile()
    text = compiled.as_text()
    kernels = text.count('custom_call_target="tpu_custom_call"')
    if grouped == "gmm":
        # what auto resolves to on the chip: the Pallas grouped matmul,
        # three products rebuilt and twice transposed, in two branches
        # (a gradient alone needs no forward product but the rebuilt ones)
        assert "ragged-dot" not in text
        assert kernels >= 2 * 8
    else:
        assert "ragged-dot" in text
    assert "conditional" in text  # the worst case waits behind a cond


def test_glyph_embedding_backward_at_the_cells_size(one_chip, no_compile_cache):
    """nethack_learner: 81 x 128 frames of 21 x 79 glyphs, 17,200,512 lookups
    of a row of 16 out of 5,976. What the scatter-add's program had and the
    product's must not: the cotangent as float32 rows of 16 (8.8 GB under an
    (8,128) tile), channel by channel as ``[1,16,lookups]``, and a scatter."""
    frames, rows, width = 81 * 128, 5976, 16
    lookups = frames * 21 * 79

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def backward(table, ids, g):
        return jax.vjp(lambda t: embed_lookup(t, ids, g.dtype), table)[1](g)

    compiled = jax.jit(backward).lower(
        s((rows, width), jnp.float32), s((frames, 21, 79), jnp.int32),
        s((frames, 21, 79, width), jnp.bfloat16),
    ).compile()
    text = compiled.as_text()
    assert "moolib.embed.grad.contract" in text
    assert f"f32[{lookups},{width}]" not in text
    assert f"f32[1,{width},{lookups}]" not in text
    assert " scatter(" not in text and "scatter-add" not in text
    assert "convolution(" in text  # the product, on the MXU
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9
