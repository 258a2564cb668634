"""The kernels of the language-model cell, and the glyph embedding's lookup
and backward of the NetHack cell, compiled for the chip at the cells' real sizes, with no
chip attached: the TPU's compiler is installed here and compiles for a
described v5e. What interpret mode cannot show
(tiling, fast memory, a kernel Mosaic refuses) fails here at no chip time.
Nothing runs, so nothing here is a result or a time.

One file, and the topology is described inside a fixture: only the worker
that is handed this file loads the TPU's library.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from moolib_tpu.ops.attention import flash_attention
from moolib_tpu.ops.embed import embed_lookup
from moolib_tpu.parallel import moe
from moolib_tpu.parallel.moe import linear_scores, moe_dropless

T, BLOCK = 8192, 512  # both decoder cells: 8,192 tokens, tiles of 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no compiler for it here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
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


# Every decoder cell's core at its backward geometry: mellum2_share8's
# windowed and full layers (4 query heads on 1 key/value head of 128),
# glm47_flash_share8's decompressed latent attention (20 heads of 192 + 64 =
# 256, as many key and value heads), xing4_share8's (32 heads of 128 + 64 =
# 192 queries and keys, one and a half lane tiles, and 128 values, over 4,096
# tokens, YaRN's score scale), evabyte_pp8's local call (32 heads of 128 over
# 16,384 bytes, the window of 2,048 as blocks, the row statistics an output
# with a cotangent), zaya1_share8's (8 / 2 heads) and solar_open2_share8's
# (8 / 1 over 4,096).
@pytest.mark.parametrize("window,H,HKV,D,DV,tokens,scale,lse", [
    (1024, 4, 1, 128, 128, T, None, False),
    (None, 4, 1, 128, 128, T, None, False),
    (None, 20, 20, 256, 256, T, None, False),
    (None, 32, 32, 192, 128, 4096, 0.1447, False),
    (2048, 32, 32, 128, 128, 16384, None, True),
    (None, 8, 2, 128, 128, T, None, False),
    (None, 8, 1, 128, 128, 4096, None, False),
    # no cell's: 8 / 1 heads over 16,384, whose dq is held four heads a pass
    (None, 8, 1, 128, 128, 16384, None, False),
])
def test_flash_kernels_compile_at_the_cells_shape(one_chip, no_compile_cache,
                                                  window, H, HKV, D, DV,
                                                  tokens, scale, lse):
    def shape(heads, width, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((1, heads, tokens, width), dtype,
                                    sharding=one_chip)

    seg = jax.ShapeDtypeStruct((1, tokens), jnp.int32, sharding=one_chip)

    def step(q, k, v, seg):
        def loss(q, k, v):
            out = flash_attention(
                q, k, v, causal=True, segment_ids=seg, window=window,
                block_q=BLOCK, block_k=BLOCK, scale=scale, return_lse=lse,
            )
            if lse:
                return out[0].astype(jnp.float32).sum() + out[1].sum()
            return out.astype(jnp.float32).sum()

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(step).lower(
        shape(H, D), shape(HKV, D), shape(HKV, DV), seg
    ).compile()
    # the forward and ONE backward, each a Mosaic kernel that took the VMEM
    # limit the code computed from the shape: a tree that kept a dQ and a
    # dK/dV kernel for any of these would count three
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    # both head sizes as given: nothing is padded on the way to a kernel
    assert f"bf16[{H},{tokens},{D}]" in text.replace(" ", "")
    assert f"bf16[{HKV},{tokens},{DV}]" in text.replace(" ", "")


def test_block_diffusions_flash_call_compiles_at_the_cells_shape(
        one_chip, no_compile_cache):
    """``sdar_share8``'s one flash call a block: the three copies as 96
    grouped query heads on the clean copy's 4 key/value heads of 128 over
    8,192 tokens, ids as group and rank (``rank_bits`` 12, not causal), the
    row statistics an output with a cotangent. A key/value head's 24 query
    heads' dq (192 MiB) does not fit the backward's fast memory at once:
    three rows of its grid hold 8 heads each, and it stays one kernel."""
    from moolib_tpu.ops.attention import _dq_passes

    tokens, H, HKV, D = T, 96, 4, 128
    assert _dq_passes(H // HKV, tokens, D, 2) == 3

    def shape(heads):
        return jax.ShapeDtypeStruct((1, heads, tokens, D), jnp.bfloat16,
                                    sharding=one_chip)

    seg = jax.ShapeDtypeStruct((1, tokens), jnp.int32, sharding=one_chip)

    def step(q, k, v, seg):
        def loss(q, k, v):
            out, lse = flash_attention(
                q, k, v, causal=False, segment_ids=seg, kv_segment_ids=seg,
                rank_bits=12, block_q=BLOCK, block_k=BLOCK, return_lse=True,
            )
            return out.astype(jnp.float32).sum() + lse.sum()

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(step).lower(
        shape(H), shape(HKV), shape(HKV), seg
    ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert f"bf16[{H},{tokens},{D}]" in text.replace(" ", "")


# mellum2_share8's expert layer (softmax top-8) by both products, and
# glm47_flash_share8's (sigmoid top-4 with a selection bias, scaled gates)
@pytest.mark.parametrize("grouped,d,f,rows,top_k,router", [
    ("gmm", 2304, 896, 20480, 8, {}),
    ("ragged_dot", 2304, 896, 20480, 8, {}),
    ("gmm", 2048, 1536, 10240, 4,
     {"scoring": "sigmoid", "gate_scale": 1.8}),
])
def test_grouped_products_compile_at_the_cells_shape(one_chip,
                                                     no_compile_cache,
                                                     grouped, d, f, rows,
                                                     top_k, router,
                                                     monkeypatch):
    # jax.default_backend() is the CPU here: say what the chip would run
    monkeypatch.setattr(moe, "resolve_grouped", lambda *a: grouped)
    E, held = 64, (0, 8)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {
        "router": s((d, E), jnp.float32),
        "w_gate": s((held[1], d, f), jnp.float32),
        "w_up": s((held[1], d, f), jnp.float32),
        "w_down": s((held[1], f, d), jnp.float32),
    }

    bias = s((E,), jnp.float32)

    def step(params, x, bias):
        def loss(params, x):
            params, kw = dict(params), dict(router)
            scores = linear_scores(
                x, params.pop("router"), kw.pop("scoring", "softmax")
            )
            y, _ = moe_dropless(
                params, x, scores, top_k=top_k, held=held, buffer_rows=rows,
                select_bias=bias if router else None, **kw,
            )
            return y.astype(jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1))(params, x)

    compiled = jax.jit(step).lower(
        params, s((T, d), jnp.bfloat16), bias
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
    # rows move by the tile, in loops the device ends: a branch's rebuilt
    # gather and the two transposes (the forward's own are dead code in a
    # gradient alone), and no gather of a whole buffer
    walks = [line for line in text.splitlines() if " while(" in line
             and re.search(r"moolib\.moe\.(gather|combine)/while", line)]
    assert len(walks) == 2 * 3
    gathered = set(re.findall(rf"= \w+\[(\d+),{d}\]\S* gather\(", text))
    assert gathered == {str(moe._WALK_TILE)}  # a tile, never a buffer


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


@pytest.mark.parametrize("chips", [1, 4])
def test_glyph_embedding_forward_at_the_cells_size(topo, no_compile_cache,
                                                   chips, monkeypatch):
    """nethack_learner's 17,200,512 lookups and the first convolution, on
    one chip and with the frames split over four inside a ``shard_map``.
    What the gather's program had and the kernel's must not: rows of 16
    under an (8,128) tile (8.8 GB as float32), their transpose to
    channel-major, and a copy on the way into the convolution; the kernel
    writes the order the convolution reads."""
    # jax.default_backend() is the CPU here: say what the chip would see
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    frames, rows, width = 81 * 128, 5976, 16
    lookups = frames * 21 * 79
    mesh = Mesh(np.array(topo.devices[:chips]), ("dp",))

    def s(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    conv = nn.Conv(32, (3, 3), strides=(2, 2), dtype=jnp.bfloat16)

    def local(table, ids, kernel, bias):
        x = embed_lookup(table, ids, jnp.bfloat16)
        y = conv.apply({"params": {"kernel": kernel, "bias": bias}}, x)
        return jax.lax.psum(nn.relu(y).astype(jnp.float32).sum(), "dp")

    forward = jax.shard_map(
        local, mesh=mesh, in_specs=(P(), P("dp"), P(), P()), out_specs=P(),
    )
    compiled = jax.jit(forward).lower(
        s((rows, width), jnp.float32), s((frames, 21, 79), jnp.int32, P("dp")),
        s((3, 3, width, 32), jnp.float32), s((32,), jnp.float32),
    ).compile()
    text = compiled.as_text()
    assert "moolib.embed.lookup.blocked" in text
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f"[{lookups // chips},{width}]" not in text  # in no dtype
    assert "gather(" not in text
    local_frames = frames // chips
    assert not re.search(
        rf"bf16\[{local_frames},21,79,{width}\]\S* (copy|transpose)\(", text
    )
    assert "convolution(" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


@pytest.mark.parametrize("repeat,policy,kernels", [
    (2, True, 2),   # forward; the one backward kernel in the backward scan
    (2, False, 3),  # the rebuild as it was: the forward kernel again
    # a block outside a scan never ran it twice: ``prevent_cse=False``
    # lets XLA merge the rebuilt kernel with the first
    (1, False, 2),
])
def test_a_rebuilt_latent_block_runs_the_forward_core_once(
        one_chip, no_compile_cache, monkeypatch, repeat, policy, kernels):
    """glm47_learner_8k's latent attention (20 heads of 256 over 8,192
    tokens in tiles of 512) in blocks rebuilt in the backward pass as
    ``remat_blocks`` rebuilds them, here with the dense MLP of 10,240: the
    gradient of a scanned stack holds the forward kernel once, because
    the rebuild reads the kept output and row statistics; without the
    policy it holds it a second time."""
    import json

    from moolib_tpu.models import lm

    if not policy:
        remat = nn.remat
        monkeypatch.setattr(nn, "remat", lambda cls, prevent_cse, policy:
                            remat(cls, prevent_cse=prevent_cse))
    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "configs",
            "glm47_flash_share8.json")) as f:
        kwargs = json.load(f)["model"]["kwargs"]
    # jax.default_backend() is the CPU here: say what the chip would run
    net = lm.decoder_lm(**dict(
        kwargs, attention_backend="flash", compute_dtype=jnp.bfloat16))
    assert (net.num_heads, net.head_dim, net.attention_block,
            net.remat_blocks) == (20, 256, BLOCK, True)

    class Stack(nn.Module):
        @nn.compact
        def __call__(self, x, seg, positions):
            return lm._blocks(
                dict(net.attention_kinds)["latent"], "dense", net._sizes(),
                repeat, net.remat_blocks, "blocks",
            )(x, seg, positions)

    stack = Stack()
    x = jax.ShapeDtypeStruct((T, 1, net.hidden_size), jnp.bfloat16,
                             sharding=one_chip)
    seg = jax.ShapeDtypeStruct((1, T), jnp.int32, sharding=one_chip)
    positions = jnp.arange(T)
    params = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip),
        jax.eval_shape(stack.init, jax.random.PRNGKey(0), x, seg, positions),
    )

    def step(params, x, seg):
        def loss(params, x):
            return stack.apply(params, x, seg, positions).astype(
                jnp.float32).sum()

        return jax.value_and_grad(loss, argnums=(0, 1))(params, x)

    text = jax.jit(step).lower(params, x, seg).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == kernels
    assert all("moolib.lm.attn_core" in line for line in calls)


@pytest.mark.parametrize("dtype,n,tokens,width", [
    ("bfloat16", 4, 4096, 3584),  # xing4_learner_4k's streams
    ("float32", 4, 4096, 3584),
    ("bfloat16", 2, 1024, 512),
])
def test_the_residual_mixings_kernels_compile_at_the_cells_shape(
        one_chip, no_compile_cache, monkeypatch, request, dtype, n, tokens,
        width):
    """The four fused passes of ``ops/hyper_mix.py`` (a sublayer's read and
    write sides, forward and backward) through the value and gradient of
    one sublayer: Mosaic takes their tiles, their transposes, the rows of the
    coefficients read in pieces and the coefficients' backward traced into
    the kernel, within the fast memory they ask for; and the program's
    temporaries stay under three arrays of the streams' size (the new
    streams, their gradient's two parts)."""
    from moolib_tpu.ops import hyper_mix

    # jax.default_backend() is the CPU here: say what the chip would run
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # The four rules are jits of their own and decide between Mosaic and
    # Pallas' interpreter when they are traced: a trace of these shapes
    # made earlier in this process under the CPU's answer (tests/
    # test_hyper_mix.py traces the cell's step) would be found again, and
    # one made here would be found by a later CPU test.
    rules = (hyper_mix._read_fwd, hyper_mix._read_bwd,
             hyper_mix._write_fwd, hyper_mix._write_bwd)
    for rule in rules:
        rule.clear_cache()
        request.addfinalizer(rule.clear_cache)
    dtype = jnp.dtype(dtype)
    assert hyper_mix.mix_path((n, tokens, width), dtype) == "fused"
    k = n * n + 2 * n

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def sublayer(x, y, phi, b, alpha):
        h, x, coef, counters = hyper_mix.read(
            x, phi, b, alpha, 1e-6, 20, 1e-6, (-30.0, 30.0))
        out = hyper_mix.write(x, coef, y + h.astype(y.dtype))
        return out.astype(jnp.float32).sum() + counters["hc_row_sum_gap"]

    compiled = jax.jit(
        jax.value_and_grad(sublayer, argnums=(0, 1, 2, 3, 4))).lower(
        s((n, tokens, width), dtype), s((tokens, width), dtype),
        s((n * width, k), jnp.float32), s((k,), jnp.float32),
        s((3,), jnp.float32),
    ).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 4
    assert sum("moolib.lm.hc_mix" in c for c in calls) == 2
    assert sum("moolib.lm.hc_post" in c for c in calls) == 2
    streams = n * tokens * width * dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * streams


def _readers(text, T, B, A):
    """The opcodes of the instructions of the entry computation that read
    its one parameter ``f32[T,B,A]``."""
    text = text[text.index("\nENTRY "):]
    names = re.findall(
        rf"(%\S+) = f32\[{T},{B},{A}\]\S* parameter\(", text)
    assert len(names) == 1, names
    return [
        m.group(1) for m in re.finditer(
            r"= \S+ ([\w-]+)\(([^)]*)\)", text)
        if names[0] in m.group(2).split(", ")
    ]


# the nine cells' behaviour logits: three a sequence's rows of a whole
# number of (8,128) tiles, the others' 19,360 and 320 actions that tile by
# nothing and IMPALA's wide batch of kilobytes
@pytest.mark.parametrize("T,B,A,path", [
    (8191, 1, 12288, "streamed"), (4095, 1, 24576, "streamed"),
    (4095, 1, 16384, "streamed"), (8191, 1, 19360, "plain"),
    (16383, 1, 320, "plain"), (20, 256, 6, "plain"),
])
def test_the_behaviour_logits_are_read_once_at_the_cells_shape(
        one_chip, no_compile_cache, monkeypatch, request, T, B, A, path):
    """``from_logits`` for a v5e with the behaviour logits the module's one
    ``[T, B, A]`` parameter, as a learn batch hands them over (the target's
    come from a product, as a head's do). Where the rule streams, the
    parameter reaches the Pallas pass through a bitcast and nothing else
    reads it: no copy, no re-layout, and not the bare ``reduce`` over
    ``{2,1,0:T(1,128)}`` that ``action_log_probs`` compiles to there (7.9 ms
    a step of two cells, PERF.md, Findings "PR 44"), which the same compile
    of the plain function shows. The other shapes hold no kernel."""
    from moolib_tpu.ops import vtrace

    # jax.default_backend() is the CPU here: say what the chip would see
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    vtrace._stream.clear_cache()  # a CPU test's trace runs the interpreter
    request.addfinalizer(vtrace._stream.clear_cache)
    assert vtrace.action_logprob_path((T, B, A), jnp.float32) == path

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def whole(behaviour, rows, head, actions, rest):
        target = (rows[..., None] * head).astype(jnp.float32)
        out = vtrace.from_logits(
            behaviour, target, actions, rest, rest, rest, rest[0])
        return out.vs, out.pg_advantages, out.log_rhos

    def plain(behaviour, actions):
        return vtrace.action_log_probs(behaviour, actions)

    args = (s((T, B, A)), s((T, B)), s((A,)), s((T, B), jnp.int32),
            s((T, B)))
    text = jax.jit(whole).lower(*args).compile().as_text()
    kernels = text.count('custom_call_target="tpu_custom_call"')
    if path == "plain":
        assert kernels == 0
        return
    assert kernels == 1
    assert "moolib.vtrace" in next(
        line for line in text.splitlines() if "tpu_custom_call" in line)
    assert set(_readers(text, T, B, A)) == {"bitcast"}
    parent = jax.jit(plain).lower(args[0], args[3]).compile().as_text()
    assert "reduce" in _readers(parent, T, B, A)


def test_evabyte_learner_16ks_step_compiles_within_the_chips_memory(
        one_chip, no_compile_cache, monkeypatch):
    """The whole train step of ``evabyte_learner_16k`` as the cell runs it
    (16,384 bytes, 821M parameters donated, every block rebuilt, both
    attention calls on the flash kernels), compiled ahead of time for a
    v5e: Mosaic takes the kernels with a strictly-earlier rank in their
    masks and the row statistics as an output, and the compiler's plan
    stays under the 15.75 GiB it gives a program."""
    import json

    from benchmark.lib import program, seeded_eva
    from moolib_tpu.learner import make_train_state

    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "configs",
            "evabyte_pp8.json")) as f:
        config = json.load(f)
    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "workloads",
            "evabyte_learner_16k.json")) as f:
        cell = json.load(f)
    net = program.build_model(config)
    shapes = seeded_eva.param_shapes(net)
    # jax.default_backend() is the CPU here: say what the chip would run
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    optimizer = program.build_optimizer(config)
    step = program.resolve(config["step_factory"])(
        program.resolve(config["apply_factory"])(net), optimizer,
        program.loss_config(config), mesh=None, donate=True,
    )

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = jax.tree_util.tree_map(
        lambda x: s(x.shape, x.dtype),
        jax.eval_shape(lambda p: make_train_state(p, optimizer), shapes),
    )
    T, B, A = cell["unroll_length"], cell["batch_per_chip"], 320
    batch = {
        "obs": s((T + 1, B), jnp.int32), "done": s((T + 1, B), jnp.bool_),
        "rewards": s((T + 1, B), jnp.float32),
        "actions": s((T, B), jnp.int32),
        "behavior_logits": s((T, B, A), jnp.float32), "core_state": (),
    }
    compiled = step.lower(state, batch).compile()
    assert compiled.memory_analysis().peak_memory_in_bytes < 15.75 * 2 ** 30
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    # a scanned block: two forward kernels, the same two again in its
    # rebuild (keep_cores false: memory), one backward kernel for each
    assert len(calls) == 6
    assert all("moolib.lm.attn_core" in line for line in calls)
    for scope in ("moolib.lm.eva_summary", "moolib.lm.eva_merge"):
        assert scope in text


def test_solar2_learner_4ks_step_compiles_within_the_chips_memory(
        one_chip, no_compile_cache, monkeypatch):
    """The whole train step of ``solar2_learner_4k`` as the cell runs it
    (4,096 tokens, 841M parameters donated, a state handed in with the
    batch, every block rebuilt from its input, the softmax block on the
    flash kernels and the three delta-rule blocks as one scan over stacked
    parameters), compiled ahead of time for a v5e: the compiler's plan
    stays under the 15.75 GiB it gives a program, and both of the rule's
    scopes are in the program."""
    import json

    from benchmark.lib import program, seeded_kda
    from moolib_tpu.learner import make_train_state

    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "configs",
            "solar_open2_share8.json")) as f:
        config = json.load(f)
    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "workloads",
            "solar2_learner_4k.json")) as f:
        cell = json.load(f)
    net = program.build_model(config)
    shapes = seeded_kda.param_shapes(net)
    # jax.default_backend() is the CPU here: say what the chip would run
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    optimizer = program.build_optimizer(config)
    step = program.resolve(config["step_factory"])(
        program.resolve(config["apply_factory"])(net), optimizer,
        program.loss_config(config), mesh=None, donate=True,
    )

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = jax.tree_util.tree_map(
        lambda x: s(x.shape, x.dtype),
        jax.eval_shape(lambda p: make_train_state(p, optimizer), shapes),
    )
    T, B, A = cell["unroll_length"], cell["batch_per_chip"], config[
        "num_actions"]
    batch = {
        "obs": s((T + 1, B), jnp.int32), "done": s((T + 1, B), jnp.bool_),
        "rewards": s((T + 1, B), jnp.float32),
        "actions": s((T, B), jnp.int32),
        "behavior_logits": s((T, B, A), jnp.float32),
        "core_state": tuple(
            s(x.shape, x.dtype) for x in net.initial_state(B)
        ),
    }
    compiled = step.lower(state, batch).compile()
    assert compiled.memory_analysis().peak_memory_in_bytes < 15.75 * 2 ** 30
    text = compiled.as_text()
    flash = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "moolib.lm.attn_core" in line]
    # the one softmax block: its forward kernel (the block is no scan's
    # body, so the compiler folds the rebuilt call into the first) and its
    # backward kernel
    assert len(flash) == 2
    for scope in ("moolib.lm.kda_core", "moolib.lm.kda_proj"):
        assert scope in text


def test_zaya1_learner_8ks_step_compiles_within_the_chips_memory(
        one_chip, no_compile_cache, monkeypatch):
    """The whole train step of ``zaya1_learner_8k`` as the cell runs it
    (8,192 tokens, 602M parameters donated, five blocks of compressed
    convolutional attention and top-1 experts as one scan over stacked
    parameters whose carry is the stream and the router's state, every
    block rebuilt but its attention core), compiled ahead of time for a
    v5e: the compiler's plan stays under the 15.75 GiB it gives a
    program, the core runs the grouped-head flash kernels at 8 / 2 heads
    of 128 (forward and backward: no forward kernel in the rebuild), the
    experts the grouped matmul, and the model's three scopes are in the
    program."""
    import json

    from benchmark.lib import program, seeded_cca
    from moolib_tpu.learner import make_train_state

    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "configs",
            "zaya1_share8.json")) as f:
        config = json.load(f)
    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "workloads",
            "zaya1_learner_8k.json")) as f:
        cell = json.load(f)
    assert config["model"]["kwargs"]["remat_blocks"] == "cores"
    net = program.build_model(config)
    shapes = seeded_cca.param_shapes(net)
    # jax.default_backend() is the CPU here: say what the chip would run
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    optimizer = program.build_optimizer(config)
    step = program.resolve(config["step_factory"])(
        program.resolve(config["apply_factory"])(net), optimizer,
        program.loss_config(config), mesh=None, donate=True,
    )

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = jax.tree_util.tree_map(
        lambda x: s(x.shape, x.dtype),
        jax.eval_shape(lambda p: make_train_state(p, optimizer), shapes),
    )
    T, B, A = cell["unroll_length"], cell["batch_per_chip"], config[
        "num_actions"]
    batch = {
        "obs": s((T + 1, B), jnp.int32), "done": s((T + 1, B), jnp.bool_),
        "rewards": s((T + 1, B), jnp.float32),
        "actions": s((T, B), jnp.int32),
        "behavior_logits": s((T, B, A), jnp.float32), "core_state": (),
    }
    compiled = step.lower(state, batch).compile()
    assert compiled.memory_analysis().peak_memory_in_bytes < 15.75 * 2 ** 30
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("moolib.lm.attn_core" in line for line in calls) == 2
    assert sum("moolib.moe.experts" in line for line in calls) == 30
    for scope in ("moolib.lm.cca_proj", "moolib.lm.cca_mix",
                  "moolib.moe.router_mlp"):
        assert scope in text
