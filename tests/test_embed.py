"""``ops/embed.py``: a lookup whose table gradient is a blocked one-hot
product for small narrow tables and the gather's own scatter-add otherwise.
The value never changes; the gradient is held to a float64 scatter-add of
the same addends."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moolib_tpu.ops import embed
from moolib_tpu.ops.embed import embed_lookup, grad_path

ROWS, WIDTH = 5976, 16  # NetHackNet's glyph table: whole blocks of LO and 88 rows


def _ids(kind, n, rows):
    rng = np.random.default_rng(3)
    if kind == "uniform":
        return rng.integers(0, rows, n)
    if kind == "all_one_id":
        return np.full(n, 2359)
    if kind == "first_and_last":
        return rng.choice([0, rows - 1], n)
    raise ValueError(kind)


def _table_grad(table, ids, g):
    out, vjp = jax.vjp(lambda t: embed_lookup(t, ids, g.dtype), table)
    assert out.dtype == g.dtype
    (d_table,) = vjp(g)
    return d_table


def _float64_grad(table, ids, g):
    """``jax.grad`` of the plain lookup, everything in float64."""
    with jax.enable_x64():
        g64 = jnp.asarray(np.asarray(g.astype(jnp.float32), np.float64))
        t64 = jnp.asarray(np.asarray(table, np.float64))
        d = jax.grad(lambda t: (t[jnp.asarray(ids)] * g64).sum())(t64)
        return np.asarray(d)


def _check(table, ids, g):
    d_table = _table_grad(table, jnp.asarray(ids, jnp.int32), g)
    assert d_table.shape == table.shape and d_table.dtype == table.dtype
    want = _float64_grad(table, ids, g)
    # float32 accumulation of at most `fullest` addends, each at most `top`
    fullest = np.bincount(np.asarray(ids).reshape(-1)).max()
    top = float(jnp.abs(g.astype(jnp.float32)).max())
    tol = fullest * np.finfo(np.float32).eps * top
    np.testing.assert_allclose(
        np.asarray(d_table, np.float64), want, rtol=0, atol=tol
    )


@pytest.mark.parametrize("cotangent", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kind", ["uniform", "all_one_id", "first_and_last"])
def test_contract_gradient_is_the_float64_scatter_add(kind, cotangent):
    assert grad_path((ROWS, WIDTH)) == "contract"
    assert ROWS % embed.LO  # the last block of rows is part empty
    n = 3 * 21 * 79
    table = jax.random.normal(jax.random.PRNGKey(0), (ROWS, WIDTH))
    g = jax.random.normal(jax.random.PRNGKey(1), (3, 21, 79, WIDTH))
    _check(table, _ids(kind, n, ROWS).reshape(3, 21, 79), g.astype(cotangent))


@pytest.mark.parametrize("cotangent", [jnp.float32, jnp.bfloat16])
def test_contract_gradient_over_a_ragged_last_block(cotangent):
    n = embed.BLOCK + 777  # two blocks, the second mostly padding
    table = jax.random.normal(jax.random.PRNGKey(0), (ROWS, WIDTH))
    g = jax.random.normal(jax.random.PRNGKey(1), (n, WIDTH))
    _check(table, _ids("uniform", n, ROWS), g.astype(cotangent))


@pytest.mark.parametrize("cotangent", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("lead,rest", [(256, (3, 7)), (4096, (6, 7))])
def test_contract_gradient_with_the_leading_axis_whole_in_a_block(
        lead, rest, cotangent):
    """A leading axis of at least a lane's width is the minor axis of a
    block: one block of 21 positions, and three of 14."""
    ids_b, g_b = embed._blocks(
        jnp.zeros((lead,) + rest, jnp.int32),
        jnp.zeros((lead,) + rest + (WIDTH,), cotangent),
    )
    assert ids_b.shape[2] == lead and g_b.shape[1:] == ids_b.shape[1:2] + (WIDTH, lead)
    assert ids_b.shape[0] == (1 if lead == 256 else 3)
    table = jax.random.normal(jax.random.PRNGKey(0), (ROWS, WIDTH))
    g = jax.random.normal(jax.random.PRNGKey(1), (lead,) + rest + (WIDTH,))
    ids = _ids("uniform", lead * rest[0] * rest[1], ROWS)
    _check(table, ids.reshape((lead,) + rest), g.astype(cotangent))


def test_gradient_inside_a_shard_map_is_summed_over_the_split_ids():
    """As the data-parallel learner step calls it: the table replicated,
    the ids split over ``dp``; JAX sums the gradient of a replicated
    argument over the axis, for the product as for the plain gather."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    table = jax.random.normal(jax.random.PRNGKey(0), (ROWS, WIDTH))
    ids = jnp.asarray(_ids("uniform", 8 * 21 * 79, ROWS).reshape(8, 21, 79),
                      jnp.int32)
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 21, 79, WIDTH))

    def grads(lookup):
        def local(table, ids, w):
            return jax.grad(lambda t: (lookup(t, ids) * w).sum())(table)

        return jax.jit(jax.shard_map(
            local, mesh=mesh, in_specs=(P(), P("dp"), P("dp")), out_specs=P(),
        ))(table, ids, w)

    got = grads(lambda t, i: embed_lookup(t, i, jnp.float32))
    want = grads(lambda t, i: t[i])
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    assert float(jnp.abs(want).max()) > 1.0


@pytest.mark.parametrize("shape", [(300, 8), (129, 40), (600, 700)])
def test_gradient_for_other_tables(shape):
    """Fewer rows than three blocks, one row past a block, and a table wide
    enough for the scatter-add."""
    table = jax.random.normal(jax.random.PRNGKey(0), shape)
    g = jax.random.normal(jax.random.PRNGKey(1), (1000, shape[1]))
    _check(table, _ids("uniform", 1000, shape[0]), g)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(ROWS, WIDTH), (600, 700)])
def test_forward_is_bitwise_the_plain_lookup(shape, dtype):
    table = jax.random.normal(jax.random.PRNGKey(0), shape)
    ids = jnp.asarray(_ids("uniform", 4 * 21 * 79, shape[0]).reshape(4, 21, 79))
    got = jax.jit(embed_lookup, static_argnums=2)(table, ids, dtype)
    want = table[ids].astype(dtype)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(
        np.asarray(got).view(np.uint8), np.asarray(want).view(np.uint8)
    )


@pytest.mark.parametrize(
    "shape,path",
    [((5976, 16), "contract"), ((12288, 2304), "scatter")],
)
def test_shape_rule_and_the_scope_that_names_it(shape, path):
    """NetHackNet's glyphs take the product, a language model's token table
    XLA's scatter-add; the lowered program says which under its scope."""
    assert grad_path(shape) == path
    table = jax.ShapeDtypeStruct(shape, jnp.float32)
    ids = jax.ShapeDtypeStruct((64,), jnp.int32)

    def loss(table, ids):
        return embed_lookup(table, ids, jnp.bfloat16).astype(jnp.float32).sum()

    text = jax.jit(jax.value_and_grad(loss)).lower(table, ids).as_text(
        debug_info=True
    )
    assert "moolib.embed.lookup" in text
    assert f"moolib.embed.grad.{path}" in text
    assert ("stablehlo.scatter" in text) == (path == "scatter")
    other = {"contract": "scatter", "scatter": "contract"}[path]
    assert f"moolib.embed.grad.{other}" not in text


# NetHackNet().init(PRNGKey(7), ...) at the parent commit (9e7803f), before
# the model called embed_lookup: path, shape, sum, first and last element.
PARENT_TREE = [
    ("params/Conv_0/bias", (32,), 0.0, 0.0, 0.0),
    ("params/Conv_0/kernel", (3, 3, 16, 32), 3.6998524515993267, 0.029309354722499847, 0.08376350998878479),
    ("params/Conv_1/bias", (64,), 0.0, 0.0, 0.0),
    ("params/Conv_1/kernel", (3, 3, 32, 64), 3.913616816373178, -0.014705000445246696, 0.0542331226170063),
    ("params/Conv_2/bias", (64,), 0.0, 0.0, 0.0),
    ("params/Conv_2/kernel", (3, 3, 64, 64), 2.616700936910071, 0.003456744132563472, 0.052817005664110184),
    ("params/Dense_0/bias", (64,), 0.0, 0.0, 0.0),
    ("params/Dense_0/kernel", (27, 64), -0.865604117035673, -0.35217714309692383, -0.388786256313324),
    ("params/Dense_1/bias", (256,), 0.0, 0.0, 0.0),
    ("params/Dense_1/kernel", (1984, 256), 21.45620507407717, 0.0009899743599817157, -0.012916585430502892),
    ("params/LSTMCore_0/Scan_MaskedLSTMStep_0/OptimizedLSTMCell_0/hf/bias", (256,), 0.0, 0.0, 0.0),
    ("params/LSTMCore_0/Scan_MaskedLSTMStep_0/OptimizedLSTMCell_0/hf/kernel", (256, 256), 29.346695828425027, -0.11637699604034424, 0.00047996360808610916),
    ("params/LSTMCore_0/Scan_MaskedLSTMStep_0/OptimizedLSTMCell_0/hg/bias", (256,), 0.0, 0.0, 0.0),
    ("params/LSTMCore_0/Scan_MaskedLSTMStep_0/OptimizedLSTMCell_0/hg/kernel", (256, 256), 6.542828942618144, -0.08676600456237793, 0.0439305454492569),
    ("params/LSTMCore_0/Scan_MaskedLSTMStep_0/OptimizedLSTMCell_0/hi/bias", (256,), 0.0, 0.0, 0.0),
    ("params/LSTMCore_0/Scan_MaskedLSTMStep_0/OptimizedLSTMCell_0/hi/kernel", (256, 256), -4.842611079524431, 0.027045130729675293, -0.022704467177391052),
    ("params/LSTMCore_0/Scan_MaskedLSTMStep_0/OptimizedLSTMCell_0/ho/bias", (256,), 0.0, 0.0, 0.0),
    ("params/LSTMCore_0/Scan_MaskedLSTMStep_0/OptimizedLSTMCell_0/ho/kernel", (256, 256), -9.304731903690254, -0.049462318420410156, -0.04061606526374817),
    ("params/LSTMCore_0/Scan_MaskedLSTMStep_0/OptimizedLSTMCell_0/if/kernel", (256, 256), 19.725069341685668, -0.039238300174474716, -0.021852880716323853),
    ("params/LSTMCore_0/Scan_MaskedLSTMStep_0/OptimizedLSTMCell_0/ig/kernel", (256, 256), 14.24109793639903, -0.024708012118935585, -0.012987629510462284),
    ("params/LSTMCore_0/Scan_MaskedLSTMStep_0/OptimizedLSTMCell_0/ii/kernel", (256, 256), 2.0685154552302834, -0.08584373444318771, 0.016267504543066025),
    ("params/LSTMCore_0/Scan_MaskedLSTMStep_0/OptimizedLSTMCell_0/io/kernel", (256, 256), -1.4333104273449493, -0.02400803565979004, 0.13310208916664124),
    ("params/baseline/bias", (1,), 0.0, 0.0, 0.0),
    ("params/baseline/kernel", (256, 1), 1.7961220685974695, 0.0009140208130702376, 0.09695901721715927),
    ("params/glyph_embed/embedding", (5976, 16), 66.38528720644831, 0.5455933213233948, -0.13980260491371155),
    ("params/policy/bias", (23,), 0.0, 0.0, 0.0),
    ("params/policy/kernel", (256, 23), -4.797836578001807, -0.08259835094213486, -0.057718995958566666),
]


def _nethack(T=2, B=3):
    from moolib_tpu.models import NetHackNet

    net = NetHackNet(num_actions=23)
    obs = {
        "glyphs": jax.random.randint(
            jax.random.PRNGKey(2), (T, B, 21, 79), 0, ROWS
        ).astype(jnp.int16),
        "blstats": jnp.full((T, B, 27), 20.0, jnp.float32),
    }
    args = (obs, jnp.zeros((T, B), bool), net.initial_state(B))
    return net, net.init(jax.random.PRNGKey(7), *args), args


def test_nethack_parameter_tree_is_the_parents():
    _, params, _ = _nethack()
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    got = []
    for path, leaf in flat:
        assert leaf.dtype == jnp.float32
        a = np.asarray(leaf, np.float64).reshape(-1)
        got.append(("/".join(p.key for p in path), tuple(leaf.shape),
                    float(a.sum()), float(a[0]), float(a[-1])))
    assert [g[:2] for g in got] == [w[:2] for w in PARENT_TREE]
    for g, w in zip(got, PARENT_TREE):
        np.testing.assert_allclose(g[2:], w[2:], rtol=1e-6, atol=0, err_msg=g[0])


def test_nethack_gradients_do_not_depend_on_the_path(monkeypatch):
    """The whole model's gradient with the product is its gradient with the
    scatter-add, to float32 summation order."""
    net, params, args = _nethack()

    def loss(params):
        (logits, baseline), _ = net.apply(params, *args)
        return (logits ** 2).mean() + (baseline ** 2).mean()

    contract = jax.grad(loss)(params)
    monkeypatch.setattr(embed, "grad_path", lambda shape: "scatter")
    scatter = jax.grad(loss)(params)
    for a, b in zip(jax.tree_util.tree_leaves(contract),
                    jax.tree_util.tree_leaves(scatter)):
        scale = float(jnp.abs(b).max()) or 1.0
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-6 * scale)
    assert float(jnp.abs(
        contract["params"]["glyph_embed"]["embedding"]).max()) > 0
