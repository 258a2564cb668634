"""The dropless expert layer (``parallel.moe.moe_dropless``) against a loop
over experts under ``jit``, forward and gradients; at an imbalance that
sends every token to one expert; with a buffer smaller than the
routing asks; and with the Pallas grouped matmul (in the interpreter) in
the place of ``ragged_dot``. And its two row movers, ``take_rows`` and
``add_rows``, against the whole-buffer gather and scatter-add they stand
for, at every count of rows in use around a tile's edge, with their
gradients in the four places ``models/lm.py`` puts the layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moolib_tpu.learner import ImpalaConfig, impala_loss
from moolib_tpu.parallel import moe
from moolib_tpu.parallel.moe import (
    add_rows, linear_scores, moe_dropless, resolve_grouped, take_rows,
)

T, D, F, E = 96, 16, 12, 8


def gated_params(seed, count=E):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {
        "router": jax.random.normal(ks[0], (D, E)) / 4,
        "w_gate": jax.random.normal(ks[1], (count, D, F)) / 4,
        "w_up": jax.random.normal(ks[2], (count, D, F)) / 4,
        "w_down": jax.random.normal(ks[3], (count, F, D)) / 3,
    }, jax.random.normal(ks[4], (T, D))


def routed(params, x, **kw):
    """The layer behind a router of one matrix, ``params["router"]``; one
    expert a token takes its gate as it is (renormalised it is 1, and
    refused)."""
    experts = {k: v for k, v in params.items() if k != "router"}
    kw.setdefault("renormalize", kw["top_k"] > 1)
    return moe_dropless(
        experts, x, linear_scores(x, params["router"]), **kw
    )


def loop_over_experts(params, x, top_k, first, count):
    probs = jax.nn.softmax(x @ params["router"], axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    gates = top_p / top_p.sum(-1, keepdims=True) if top_k > 1 else top_p
    y = jnp.zeros_like(x)
    for e in range(count):
        g = jnp.sum(jnp.where(top_i == first + e, gates, 0.0), axis=-1)
        h = jax.nn.silu(x @ params["w_gate"][e]) * (x @ params["w_up"][e])
        y = y + g[:, None] * (h @ params["w_down"][e])
    return y


@pytest.mark.parametrize(
    "top_k,held", [(2, None), (3, (2, 4)), (1, (6, 2)), (1, None)]
)
def test_equal_to_a_loop_over_experts_under_jit(top_k, held):
    first, count = held or (0, E)
    params, x = gated_params(0, count)
    ours = jax.jit(
        lambda p, x: routed(p, x, top_k=top_k, held=held)[0]
    )
    ref = lambda p, x: loop_over_experts(p, x, top_k, first, count)  # noqa
    np.testing.assert_allclose(ours(params, x), ref(params, x),
                               rtol=1e-5, atol=1e-5)
    w = jax.random.normal(jax.random.PRNGKey(9), (T, D))
    g_ours = jax.jit(jax.grad(
        lambda p, x: jnp.sum(w * ours(p, x)), argnums=(0, 1)
    ))(params, x)
    g_ref = jax.grad(
        lambda p, x: jnp.sum(w * ref(p, x)), argnums=(0, 1)
    )(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(g_ours),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_every_token_to_one_expert_and_nothing_dropped():
    params, x = gated_params(3)
    x = x.at[:, 0].set(5.0)
    router = jnp.zeros((D, E)).at[0, 5].set(9.0).at[0, 1].set(4.0)
    params = dict(params, router=router)
    y, counters = jax.jit(
        lambda p, x: routed(p, x, top_k=2)
    )(params, x)
    np.testing.assert_allclose(
        y, loop_over_experts(params, x, 2, 0, E), rtol=1e-5, atol=1e-5
    )
    assert float(counters["moe_load_max"]) == T  # one expert, every token
    assert float(counters["moe_overflow"]) == 0.0
    assert float(counters["moe_tokens_unserved"]) == 0.0


@pytest.mark.parametrize("rows,spills", [
    (None, 0.0), ("held", 0.0), ("held - 1", 1.0), (8, 1.0), (10**6, 0.0),
])
def test_a_buffer_too_small_spills_to_the_worst_case(rows, spills):
    """``buffer_rows`` is the size that usually does: routing that fits it
    runs over it, routing that does not over the worst case, and the result
    and its gradients are the unbounded layer's whichever runs."""
    params, x = gated_params(5, count=3)
    kw = dict(top_k=2, held=(1, 3))
    full, counters = routed(params, x, **kw)
    held = int(counters["moe_assignments_held"])
    rows = eval(rows, {"held": held}) if isinstance(rows, str) else rows
    w = jax.random.normal(jax.random.PRNGKey(8), (T, D))

    def loss(rows):
        return lambda p, x: jnp.sum(w * routed(
            p, x, buffer_rows=rows, **kw)[0])

    g_full = jax.grad(loss(None), argnums=(0, 1))(params, x)
    y, c = jax.jit(lambda p, x: routed(
        p, x, buffer_rows=rows, **kw))(params, x)
    np.testing.assert_allclose(y, full, rtol=1e-5, atol=1e-6)
    assert float(c["moe_spills"]) == spills
    assert float(c["moe_overflow"]) == 0.0
    g = jax.jit(jax.grad(loss(rows), argnums=(0, 1)))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_full)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_a_stated_buffer_keeps_none_of_its_rows_for_the_backward_pass():
    """Two buffers behind a cond rebuild their rows in the backward pass:
    the products run forward, again, and twice transposed, in each
    branch. One buffer keeps its rows and runs them once forward."""
    params, x = gated_params(6, count=3)

    def products(rows):
        text = str(jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(routed(
            p, x, top_k=2, held=(1, 3), buffer_rows=rows)[0]),
            argnums=(0, 1)))(params, x))
        return text.count("= ragged_dot_general["), text.count("= cond[")

    kept, conds = products(None)
    assert conds == 0 and products(10**6) == (kept, 0)
    rebuilt, conds = products(16)
    assert conds >= 2 and rebuilt == 2 * (kept + 3)


@pytest.mark.parametrize("held,buffer_rows", [(None, None), ((1, 3), 128)])
def test_the_pallas_grouped_matmul_equals_ragged_dot(held, buffer_rows,
                                                     monkeypatch):
    """Widths and rows that tile (lanes of 128), the kernels in the Pallas
    interpreter: forward and gradients equal ``ragged_dot``'s, in both
    branches of a stated buffer."""
    d, f, e, top_k = 128, 256, 4, 2
    count = e if held is None else held[1]
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    params = {
        "router": jax.random.normal(ks[0], (d, e)) / 8,
        "w_gate": jax.random.normal(ks[1], (count, d, f)) / 11,
        "w_up": jax.random.normal(ks[2], (count, d, f)) / 11,
        "w_down": jax.random.normal(ks[3], (count, f, d)) / 16,
    }
    x = jax.random.normal(ks[4], (128, d))  # 128 tokens: 256 rows at worst
    w = jax.random.normal(jax.random.PRNGKey(12), x.shape)

    def both(how):
        monkeypatch.setattr(moe, "resolve_grouped", lambda *a: how)

        def loss(p, x):
            y, aux = routed(p, x, top_k=top_k, held=held,
                                  buffer_rows=buffer_rows)
            return jnp.sum(w * y), (y, aux)

        (_, (y, aux)), grads = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
        )(params, x)
        return y, aux, grads

    y_r, aux_r, g_r = both("ragged_dot")
    y_g, aux_g, g_g = both("gmm_interpret")
    assert float(aux_g["moe_overflow"]) == 0
    assert float(aux_g["moe_spills"]) == float(aux_r["moe_spills"])
    np.testing.assert_allclose(y_g, y_r, rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g_g),
                    jax.tree_util.tree_leaves(g_r)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


# A buffer of five tiles over 300 tokens, so tokens repeat; the width is
# small, the tile the one the chip walks.
TILE = moe._WALK_TILE
WALK_ROWS = 5 * TILE
WALK_T, WALK_D = 300, 8


def walk_inputs(seed=21):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    token = jax.random.randint(ks[0], (WALK_ROWS,), 0, WALK_T)
    x = jax.random.normal(ks[1], (WALK_T, WALK_D))
    v = jax.random.normal(ks[2], (WALK_ROWS, WALK_D))
    w = jax.random.normal(ks[3], (WALK_ROWS, WALK_D))
    return token, x, v, w


@pytest.mark.parametrize("rows,tokens,width,dtype,loops", [
    (20480, 8192, 2304, jnp.bfloat16, True),   # mellum2_share8: 2.5 a token
    (10240, 8192, 2048, jnp.bfloat16, True),   # glm47_flash_share8: 1.25
    (5120, 4096, 3584, jnp.bfloat16, True),    # xing4_share8: 1.25
    (4608, 8192, 2048, jnp.bfloat16, False),   # zaya1_share8: 0.56
    (2048, 4096, 4096, jnp.bfloat16, False),   # solar_open2_share8: 0.5
    (20480, 8192, 8192, jnp.bfloat16, False),  # a tile of 8 MB: not read
    (20480, 8192, 2304, jnp.float32, False),   # the same in bytes
    (20000, 8192, 2304, jnp.bfloat16, False),  # no multiple of the tile
])
def test_the_walk_is_a_loop_where_the_chip_read_a_gain(rows, tokens, width,
                                                       dtype, loops):
    tile = moe._walk_tile(rows, tokens, width, dtype)
    assert tile == (TILE if loops else rows)


def plain_take(x, token, n):
    return jnp.where((jnp.arange(token.shape[0]) < n)[:, None], x[token], 0)


def plain_add(v, token, n, T):
    used = (jnp.arange(token.shape[0]) < n)[:, None]
    return jnp.zeros((T, v.shape[1]), v.dtype).at[token].add(
        jnp.where(used, v, 0)
    )


@pytest.mark.parametrize("n", [
    0, 1, moe._WALK_TILE - 1, moe._WALK_TILE, moe._WALK_TILE + 1, WALK_ROWS,
])
def test_the_row_movers_equal_the_whole_buffer_gather_and_scatter_add(n):
    assert moe._walk_tile(WALK_ROWS, WALK_T, WALK_D, jnp.float32) == TILE
    token, x, v, _ = walk_inputs()
    n = jnp.asarray(n, jnp.int32)
    np.testing.assert_array_equal(
        jax.jit(take_rows, static_argnums=3)(x, token, n, TILE),
        plain_take(x, token, n)
    )
    np.testing.assert_allclose(
        jax.jit(add_rows, static_argnums=(3, 4))(v, token, n, WALK_T, TILE),
        plain_add(v, token, n, WALK_T), rtol=1e-5, atol=1e-5,
    )
    # bfloat16 rows, as the cells move them: the gather is still exact
    np.testing.assert_array_equal(
        take_rows(x.astype(jnp.bfloat16), token, n, TILE).astype(jnp.float32),
        plain_take(x.astype(jnp.bfloat16), token, n).astype(jnp.float32),
    )


def _in_cond(f):
    return lambda a, token, n: jax.lax.cond(
        n > 7, f, lambda a, token, n: 2 * f(a, token, n), a, token, n
    )


def _in_scan(f):
    def scanned(a, token, n):
        def body(scale, _):
            return scale * 0.5, scale * f(a, token, n)
        return jnp.sum(jax.lax.scan(body, 1.0, None, length=2)[1], axis=0)
    return scanned


@pytest.mark.parametrize("place", [
    jax.jit, jax.checkpoint, _in_cond, _in_scan,
])
@pytest.mark.parametrize("mover", ["take_rows", "add_rows"])
def test_the_row_movers_gradients_where_the_model_puts_the_layer(mover,
                                                                 place):
    """Each mover's backward rule is the other one, with a trip count read
    on the device: its gradient equals the plain composition's under
    ``jit``, rebuilt by ``checkpoint``, behind a ``cond`` and in a ``scan``'s
    body."""
    token, x, v, w = walk_inputs()
    n = jnp.asarray(2 * moe._WALK_TILE + 5, jnp.int32)
    if mover == "take_rows":
        ours = lambda x, token, n: take_rows(x, token, n, TILE)  # noqa
        plain, a, weight = plain_take, x, w
    else:
        ours = lambda v, token, n: add_rows(v, token, n, WALK_T, TILE)  # noqa
        plain = lambda v, token, n: plain_add(v, token, n, WALK_T)  # noqa
        a, weight = v, x

    def grad_of(f):
        return jax.jit(jax.grad(
            lambda a: jnp.sum(weight * place(f)(a, token, n))
        ))(a)

    np.testing.assert_allclose(
        grad_of(ours), grad_of(plain), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("buffer_rows", [None, 8 * moe._WALK_TILE])
def test_rows_moved_counts_the_tiles_that_hold_an_assignment(buffer_rows,
                                                             monkeypatch):
    """A drawn routing over 4,096 tokens, top-2, two experts of eight held
    here: the gather walks the tiles that hold an assignment, whole, and
    never more than the buffer; the layer's answer is one whole walk's."""
    tile, tokens = moe._WALK_TILE, 4096
    ks = jax.random.split(jax.random.PRNGKey(17), 5)
    params = {
        "w_gate": jax.random.normal(ks[1], (2, D, F)) / 4,
        "w_up": jax.random.normal(ks[2], (2, D, F)) / 4,
        "w_down": jax.random.normal(ks[3], (2, F, D)) / 3,
    }
    x = jax.random.normal(ks[4], (tokens, D))
    scores = linear_scores(x, jax.random.normal(ks[0], (D, E)) / 4)

    def layer(p, x, s):
        return moe_dropless(
            p, x, s, top_k=2, held=(3, 2), buffer_rows=buffer_rows
        )

    y, aux = jax.jit(layer)(params, x, scores)
    held = int(aux["moe_assignments_held"])
    rows = buffer_rows or 2 * tokens
    assert 0 < held < rows - tile and float(aux["moe_spills"]) == 0
    assert float(aux["moe_rows_moved"]) == -(-held // tile) * tile <= rows
    monkeypatch.setattr(moe, "_walk_tile", lambda rows, *_: rows)
    whole, aux = jax.jit(lambda *a: layer(*a))(params, x, scores)  # anew
    assert float(aux["moe_rows_moved"]) == rows
    np.testing.assert_allclose(y, whole, rtol=1e-5, atol=1e-6)


def test_auto_is_ragged_dot_off_the_chip_and_the_router_load_is_whole():
    assert resolve_grouped(16384, 2304, 896, jnp.bfloat16) == "ragged_dot"
    params, x = gated_params(8, count=3)
    _, aux = routed(params, x, top_k=3, held=(2, 3))
    load = np.asarray(aux["moe_router_load"])
    assert load.shape == (E,) and load.sum() == T * 3
    assert load[2:5].sum() == float(aux["moe_assignments_held"])


def test_held_has_to_match_the_expert_rows():
    params, x = gated_params(4, count=3)
    with pytest.raises(ValueError, match="held"):
        routed(params, x, top_k=2, held=(6, 3))
    with pytest.raises(ValueError, match="held"):
        routed(params, x, top_k=2, held=(1, 4))


def test_impala_loss_passes_counters_through_and_folds_only_loss_terms():
    """``mtp_loss`` is the one entry of a model's aux that the loss folds,
    by ``mtp_cost``; every other key is a counter, whatever its name."""
    Tn, B, A = 4, 3, 5
    batch = {
        "obs": jnp.zeros((Tn + 1, B, 2)), "done": jnp.zeros((Tn + 1, B), bool),
        "rewards": jnp.ones((Tn + 1, B)),
        "actions": jnp.zeros((Tn, B), jnp.int32),
        "behavior_logits": jnp.zeros((Tn, B, A)), "core_state": (),
    }

    def apply_with(aux):
        def apply(params, obs, done, state):
            logits = jnp.zeros((Tn + 1, B, A)) + params
            return (logits, jnp.zeros((Tn + 1, B))), state, aux
        return apply

    cfg = ImpalaConfig(mtp_cost=0.5)
    plain, m0 = impala_loss(jnp.float32(0.1), apply_with({}), batch, cfg)
    total, m1 = impala_loss(
        jnp.float32(0.1), apply_with({"moe_overflow": jnp.float32(3.0)}),
        batch, cfg,
    )
    assert float(total) == float(plain) and float(m1["moe_overflow"]) == 3.0
    total, m2 = impala_loss(
        jnp.float32(0.1),
        apply_with({"mtp_loss": jnp.float32(2.0),
                    "load_balance_loss": jnp.float32(4.0),
                    "moe_tokens_unserved": jnp.float32(7.0)}),
        batch, cfg,
    )
    assert float(total) == pytest.approx(float(plain) + 0.5 * 2)
    assert float(m2["total_loss"]) == float(total)
    assert float(m2["mtp_loss"]) == 2.0
    assert float(m2["load_balance_loss"]) == 4.0
    assert float(m2["moe_tokens_unserved"]) == 7.0
