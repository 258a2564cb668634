"""The residual mixing of a skeleton with several streams as fused passes.

A sublayer on ``n`` residual streams ``X [n, N, C]`` (``N`` tokens) reads a
weighted sum of them and writes back through a gate while the streams are
remixed (``models/transformer.py:hyper_coefficients`` and
``hyper_residual_block`` state the mathematics, and stay the path for
shapes that do not tile and the oracle of the tests):

    read:   pre, post, res = coefficients(X);  h = sum_i pre[i] X[i]
    write:  X'[i] = sum_j res[i, j] X[j] + post[i] y

As plain XLA every part casts the whole carry to float32 and writes it out,
one stream's slice at a time in the backward pass: 105 ms of a 357 ms step
at four streams of 3,584 over 4,096 tokens on one TPU v5e, ten times what
the bytes cost (PERF.md, Findings "PR 33" and "PR 35"). Here each side of
the sublayer is one Pallas pass forward and one backward over a tile of
tokens, which reads the streams once in their own dtype, does its sums in
float32 in registers and writes what leaves in the dtype it is stored in:

- :func:`read` forward: sum of squares, the ``phi`` product (on the MXU;
  for bfloat16 streams ``phi``'s float32 as three bfloat16 parts side by
  side on the output lanes, accumulated in float32: the streams are exact
  in bfloat16, so that is ``Precision.HIGHEST``'s result in one pass), the
  ``n^2 + 2n`` coefficients a token with the tokens on the lanes (sigmoids,
  the clipped exponential, every Sinkhorn iteration), the three counters,
  and ``h`` in float32.
- :func:`write` forward: the ``n`` new streams, written as ``[n, N, C]``.
- :func:`write` backward: ``dy``, the remix's part of ``dX``, ``dpost`` and
  ``dres`` (``n^2 + n`` sums over the width, accumulated lane-wise in VMEM,
  one transpose and reduction a tile).
- :func:`read` backward, which runs once the sublayer's own backward has
  given ``dh``: ``dpre``, the coefficients' backward through every
  iteration and the clip (``jax.vjp`` of the same few lines, traced into
  the kernel), ``dphi`` accumulated over the token tiles in float32, and
  the rest of ``dX`` added to the remix's part, which reaches it as the
  cotangent of the streams that :func:`read` hands on unchanged.

What the two ``custom_vjp``s keep: the streams as stored, ``y``, the
coefficients ``[n^2 + 2n, N]`` and the product and sum of squares they came
from. No float32 copy of the carry exists anywhere.

:func:`mix_path` says from a call's shapes, dtype and platform whether
these kernels run (``"fused"``) or the plain functions (``"plain"``), and
:func:`traced_path` puts that on record where the call is traced:
``residual_mix_calls_traced_total{path=}``, beside the attention
dispatcher's ``attention_calls_traced_total{backend=}``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry import global_telemetry

__all__ = ["mix_path", "read", "traced_path", "write"]

LANES = 128
ROWS = 16  # tokens to a step of the inner loops: one packed bfloat16 tile
# Elements of the streams to a tile of tokens. The read side's backward
# holds the streams, the remix's part of their gradient and the gradient it
# writes, each twice (the pipeline's two buffers), and ``dh``: at four
# streams of 3,584 a tile of 128 tokens is 43 MB of VMEM with ``phi`` and
# its gradient beside them.
TILE_ELEMENTS = 2 * 1024 * 1024
VMEM_LIMIT = 100 * 1024 * 1024
F32 = jnp.float32
BF16 = jnp.bfloat16


def coefficient_rows(n: int) -> int:
    return n * n + 2 * n


def token_tile(n: int, N: int, C: int):
    """The tokens to a grid step: a multiple of the lanes that divides
    ``N``, the largest whose streams fit :data:`TILE_ELEMENTS`; ``None``
    where there is none."""
    for tile in (512, 256, 128):
        if N % tile == 0 and n * tile * C <= TILE_ELEMENTS:
            return tile
    return None


def mix_path(shape, dtype) -> str:
    """``"fused"`` or ``"plain"``: how the mixing of streams ``[n, N, C]``
    of ``dtype`` is computed, from what a trace can see. The kernels lay
    the width along the lanes in whole tiles and the tokens of a tile along
    the lanes of the coefficients, keep the ``n^2 + 2n`` coefficient rows
    in whole sublane tiles with three bfloat16 parts of ``phi`` side by
    side on 128 lanes (``n`` 2 or 4), know bfloat16 and float32 streams,
    and Mosaic compiles them for a TPU alone."""
    n, N, C = shape
    k = coefficient_rows(n)
    fused = (
        jax.default_backend() == "tpu"
        and jnp.dtype(dtype) in (jnp.dtype(BF16), jnp.dtype(F32))
        and k % 8 == 0
        and 3 * k <= LANES
        and C % LANES == 0
        and token_tile(n, N, C) is not None
    )
    return "fused" if fused else "plain"


def traced_path(shape, dtype) -> str:
    """:func:`mix_path`, counted where a sublayer's mixing is traced (once
    a compile under jit) in ``residual_mix_calls_traced_total{path=}``."""
    path = mix_path(shape, dtype)
    global_telemetry().registry.counter(
        "residual_mix_calls_traced_total", path=path
    ).inc()
    return path


# ---------------------------------------------------------------- pieces


def _split3(a):
    """float32 ``a`` as three values, each exact in bfloat16, that sum to
    it exactly: the top eight bits of the mantissa, of what is left, and of
    what is left then. By masks, so that no pass that drops a round trip
    through bfloat16 can undo it."""
    def top(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
        return jax.lax.bitcast_convert_type(
            bits & jnp.uint32(0xFFFF0000), F32
        )

    a1 = top(a)
    a2 = top(a - a1)
    return a1, a2, a - a1 - a2


def _coefficients(z_pre, z_post, z_res, *, iters, eps, clamp):
    """The coefficients from their logits, the tokens on the minor axis:
    ``z_pre`` / ``z_post`` ``[n, t]``, ``z_res`` a list of the remix
    matrix's rows, each ``[n, t]`` (row i's entry j on the second-minor
    axis). Returns ``pre``, ``post`` and the rows of ``res``."""
    pre = jax.nn.sigmoid(z_pre)
    post = 2.0 * jax.nn.sigmoid(z_post)
    lo, hi = clamp
    res = [jnp.exp(jnp.clip(z, lo, hi)) for z in z_res]
    for _ in range(iters):
        res = [r / (jnp.sum(r, axis=0, keepdims=True) + eps) for r in res]
        columns = functools.reduce(jnp.add, res) + eps
        res = [r / columns for r in res]
    return pre, post, res


def _logit_pieces(ref, n):
    """The rows of a ``[n^2 + 2n, t]`` ref as :func:`_coefficients` takes
    them."""
    return (ref[0:n, :], ref[n:2 * n, :],
            [ref[(2 + i) * n:(3 + i) * n, :] for i in range(n)])


def _store_pieces(ref, n, pre, post, res):
    ref[0:n, :] = pre
    ref[n:2 * n, :] = post
    for i in range(n):
        ref[(2 + i) * n:(3 + i) * n, :] = res[i]


def _rows(r):
    return pl.ds(pl.multiple_of(r * ROWS, ROWS), ROWS)


def _column(ct_ref, rows, k):
    """Coefficient ``k`` of the tokens ``rows``, along the lanes of one
    tile: ``ct_ref`` holds the coefficients transposed, ``[t, 128]``."""
    return jnp.broadcast_to(ct_ref[rows, k:k + 1], (ROWS, LANES))


def _transposed(pad_ref, ct_ref, coef):
    """``coef [k, t]`` (tokens on the lanes) into ``ct_ref [t, 128]``
    (tokens on the sublanes, where a stream's tile has them)."""
    pad_ref[...] = jnp.zeros(pad_ref.shape, F32)
    pad_ref[0:coef.shape[0], :] = coef
    ct_ref[...] = pad_ref[...].T


def _lane_sums(part_ref):
    """``part_ref [t, 128]`` holds sums over the width but for the last
    128 lanes: finish them, the tokens on the lanes, ``[1, t]``."""
    return jnp.sum(part_ref[...].T, axis=0, keepdims=True)


def _inv_rms(ss, n, C, norm_eps):
    return jax.lax.rsqrt(ss * (1.0 / (n * C)) + norm_eps)


# ---------------------------------------------------------------- kernels


def _read_kernel(x_ref, phi_ref, a_ref, b_ref, h_ref, coef_ref, m_ref,
                 stat_ref, part_ref, pad_ref, ct_ref, *, n, parts, norm_eps,
                 iters, eps, clamp):
    tn, C = h_ref.shape
    k = coefficient_rows(n)
    highest = jax.lax.Precision.HIGHEST if parts == 1 else None

    def squares(r, carry):
        rows = _rows(r)
        acc = [jnp.zeros((ROWS, LANES), F32)] * n  # n chains, not one
        for c in range(0, C, LANES):
            for i in range(n):
                xv = x_ref[i, rows, c:c + LANES].astype(F32)
                acc[i] = acc[i] + xv * xv
        part_ref[rows, :] = functools.reduce(jnp.add, acc)
        return carry

    jax.lax.fori_loop(0, tn // ROWS, squares, None)
    ss = _lane_sums(part_ref)
    prod = jnp.dot(x_ref[0], phi_ref[0], preferred_element_type=F32,
                   precision=highest)
    for i in range(1, n):
        prod = prod + jnp.dot(x_ref[i], phi_ref[i],
                              preferred_element_type=F32, precision=highest)
    prod = prod.T  # [128, tn]: phi's parts down the sublanes
    m = prod[0:k]
    for p in range(1, parts):
        m = m + prod[p * k:(p + 1) * k]
    m_ref[...] = m
    # the logits, through a ref: its rows are read back in pieces
    coef_ref[...] = a_ref[...] * (m * _inv_rms(ss, n, C, norm_eps)) + b_ref[...]
    z_pre, z_post, z_res = _logit_pieces(coef_ref, n)
    pre, post, res = _coefficients(
        z_pre, z_post, z_res, iters=iters, eps=eps, clamp=clamp)
    lo, hi = clamp
    row_gap = functools.reduce(jnp.maximum, [
        jnp.abs(jnp.sum(r, axis=0, keepdims=True) - 1.0) for r in res])
    col_gap = jnp.max(
        jnp.abs(functools.reduce(jnp.add, res) - 1.0), axis=0, keepdims=True)
    clamped = functools.reduce(jnp.add, [
        jnp.sum(jnp.logical_or(z <= lo, z >= hi).astype(F32), axis=0,
                keepdims=True) for z in z_res])
    stat_ref[0:1, :] = ss
    stat_ref[1:2, :] = row_gap
    stat_ref[2:3, :] = col_gap
    stat_ref[3:4, :] = clamped
    stat_ref[4:8, :] = jnp.zeros((4, tn), F32)
    _store_pieces(coef_ref, n, pre, post, res)
    _transposed(pad_ref, ct_ref, coef_ref[...])

    def weighted(r, carry):
        rows = _rows(r)
        weight = [_column(ct_ref, rows, i) for i in range(n)]
        for c in range(0, C, LANES):
            acc = weight[0] * x_ref[0, rows, c:c + LANES].astype(F32)
            for i in range(1, n):
                acc = acc + weight[i] * x_ref[i, rows, c:c + LANES].astype(F32)
            h_ref[rows, c:c + LANES] = acc
        return carry

    jax.lax.fori_loop(0, tn // ROWS, weighted, None)


def _write_kernel(x_ref, y_ref, coef_ref, out_ref, pad_ref, ct_ref, *, n):
    tn, C = y_ref.shape
    _transposed(pad_ref, ct_ref, coef_ref[...])

    def remix(r, carry):
        rows = _rows(r)
        post = [_column(ct_ref, rows, n + i) for i in range(n)]
        res = [[_column(ct_ref, rows, (2 + i) * n + j) for j in range(n)]
               for i in range(n)]
        for c in range(0, C, LANES):
            x = [x_ref[j, rows, c:c + LANES].astype(F32) for j in range(n)]
            y = y_ref[rows, c:c + LANES].astype(F32)
            for i in range(n):
                acc = res[i][0] * x[0]
                for j in range(1, n):
                    acc = acc + res[i][j] * x[j]
                out_ref[i, rows, c:c + LANES] = (
                    acc + post[i] * y).astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, tn // ROWS, remix, None)


def _write_bwd_kernel(g_ref, x_ref, y_ref, coef_ref, dx_ref, dy_ref,
                      dcoef_ref, pad_ref, ct_ref, acc_ref, *, n):
    tn, C = y_ref.shape
    _transposed(pad_ref, ct_ref, coef_ref[...])

    def rows_of(r, carry):
        rows = _rows(r)
        post = [_column(ct_ref, rows, n + i) for i in range(n)]
        res = [[_column(ct_ref, rows, (2 + i) * n + j) for j in range(n)]
               for i in range(n)]
        zero = jnp.zeros((ROWS, LANES), F32)
        d_post = [zero] * n
        d_res = [[zero] * n for _ in range(n)]
        for c in range(0, C, LANES):
            g = [g_ref[i, rows, c:c + LANES].astype(F32) for i in range(n)]
            x = [x_ref[j, rows, c:c + LANES].astype(F32) for j in range(n)]
            y = y_ref[rows, c:c + LANES].astype(F32)
            dy = post[0] * g[0]
            for i in range(1, n):
                dy = dy + post[i] * g[i]
            dy_ref[rows, c:c + LANES] = dy.astype(dy_ref.dtype)
            for j in range(n):
                acc = res[0][j] * g[0]
                for i in range(1, n):
                    acc = acc + res[i][j] * g[i]
                dx_ref[j, rows, c:c + LANES] = acc.astype(dx_ref.dtype)
            for i in range(n):
                d_post[i] = d_post[i] + g[i] * y
                for j in range(n):
                    d_res[i][j] = d_res[i][j] + g[i] * x[j]
        for i in range(n):
            acc_ref[i, rows, :] = d_post[i]
            for j in range(n):
                acc_ref[(1 + i) * n + j, rows, :] = d_res[i][j]
        return carry

    jax.lax.fori_loop(0, tn // ROWS, rows_of, None)
    dcoef_ref[0:n, :] = jnp.zeros((n, tn), F32)
    for q in range(n * n + n):
        dcoef_ref[n + q:n + q + 1, :] = _lane_sums(acc_ref.at[q])


def _read_bwd_kernel(dh_ref, x_ref, dxa_ref, dcoef_ref, m_ref, stat_ref,
                     phit_ref, a_ref, b_ref, dx_ref, dphi_ref, dz_ref,
                     acc_ref, pad_ref, ct_ref, u_ref, *, n, parts, norm_eps,
                     iters, eps, clamp):
    tn, C = dh_ref.shape
    k = coefficient_rows(n)

    @pl.when(pl.program_id(0) == 0)
    def _():
        dphi_ref[...] = jnp.zeros(dphi_ref.shape, F32)

    def products(r, carry):  # dpre[i] = sum over the width of dh x[i]
        rows = _rows(r)
        acc = [jnp.zeros((ROWS, LANES), F32)] * n
        for c in range(0, C, LANES):
            dh = dh_ref[rows, c:c + LANES]
            for i in range(n):
                acc[i] = acc[i] + dh * x_ref[i, rows, c:c + LANES].astype(F32)
        for i in range(n):
            acc_ref[i, rows, :] = acc[i]
        return carry

    jax.lax.fori_loop(0, tn // ROWS, products, None)
    # the coefficients again from the product and the sum of squares the
    # forward kept, and their backward; dz_ref holds the logits meanwhile
    m = m_ref[...]
    inv = _inv_rms(stat_ref[0:1, :], n, C, norm_eps)
    a = a_ref[...]
    dz_ref[...] = a * (m * inv) + b_ref[...]
    (pre, _, _), back = jax.vjp(
        functools.partial(_coefficients, iters=iters, eps=eps, clamp=clamp),
        *_logit_pieces(dz_ref, n))
    for i in range(n):
        pad_ref[i:i + 1, :] = _lane_sums(acc_ref.at[i])
    d_pre, d_post, d_res = _logit_pieces(dcoef_ref, n)
    _store_pieces(dz_ref, n, *back((d_pre + pad_ref[0:n, :], d_post, d_res)))
    d_ms = a * dz_ref[...]
    d_m = d_ms * inv  # [k, tn]
    d_inv = jnp.sum(d_ms * m, axis=0, keepdims=True)
    # ss -> inv = (ss / (n C) + norm_eps)^-1/2; x enters ss squared
    d_ss2 = d_inv * inv * inv * inv * (-1.0 / (n * C))
    # what the last loop needs a token, tokens on the sublanes: pre, d_ss2
    pad_ref[...] = jnp.zeros(pad_ref.shape, F32)
    pad_ref[0:n, :] = pre
    pad_ref[n:n + 1, :] = d_ss2
    ct_ref[...] = pad_ref[...].T
    # d_m as the left side of two products: against the streams (dphi,
    # summed over the tokens) and, transposed, against phi (into dX)
    pad_ref[...] = jnp.zeros(pad_ref.shape, F32)
    rows = dphi_ref.shape[1]
    if parts == 1:
        pad_ref[0:k, :] = d_m
        left = pad_ref[0:rows, :]
        lhs = pad_ref[...].T  # [tn, 128]
        highest = jax.lax.Precision.HIGHEST
    else:
        d1, d2, d3 = _split3(d_m)
        pad_ref[0:k, :] = d1
        pad_ref[k:2 * k, :] = d2
        pad_ref[2 * k:3 * k, :] = d3
        left = pad_ref[0:rows, :].astype(BF16)
        # d_m phi^T to 2^-16: d1 p1 + d1 p2 + d2 p1, the three along the
        # contraction against phit_ref's rows [p1; p2; p1]
        pad_ref[k:2 * k, :] = d1
        pad_ref[2 * k:3 * k, :] = d2
        lhs = pad_ref[...].T.astype(BF16)
        highest = None
    for i in range(n):
        dphi_ref[i] += jnp.dot(left, x_ref[i], preferred_element_type=F32,
                               precision=highest)
        u_ref[...] = jnp.dot(lhs, phit_ref[i], preferred_element_type=F32,
                             precision=highest)

        def rows_of(r, carry):
            rows = _rows(r)
            pre_i = _column(ct_ref, rows, i)
            ss2 = _column(ct_ref, rows, n)
            for c in range(0, C, LANES):
                at = (rows, slice(c, c + LANES))
                x = x_ref[(i,) + at].astype(F32)
                dx = (dxa_ref[(i,) + at].astype(F32) + pre_i * dh_ref[at]
                      + ss2 * x + u_ref[at])
                dx_ref[(i,) + at] = dx.astype(dx_ref.dtype)
            return carry

        jax.lax.fori_loop(0, tn // ROWS, rows_of, None)


# ------------------------------------------------------------------ calls


def _call(kernel, *, grid, in_specs, out_specs, out_shape, scratch_shapes,
          sequential=False):
    return pl.pallas_call(
        kernel, name="hyper_mix" + kernel.func.__name__[:-len("_kernel")],
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "arbitrary" if sequential else "parallel",),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        # Mosaic compiles for a TPU alone. mix_path sends every other
        # backend to the plain functions, so only a test gets here off one:
        # Pallas' interpreter then runs the same arithmetic.
        interpret=jax.default_backend() != "tpu",
    )


def _specs(n, tn, C):
    k = coefficient_rows(n)
    return {
        "streams": pl.BlockSpec((n, tn, C), lambda t: (0, t, 0)),
        "width": pl.BlockSpec((tn, C), lambda t: (t, 0)),
        "rows": pl.BlockSpec((k, tn), lambda t: (0, t)),
        "stat": pl.BlockSpec((8, tn), lambda t: (0, t)),
        "scale": pl.BlockSpec((k, 1), lambda t: (0, 0)),
    }


def _transpose_scratch(tn):
    return [pltpu.VMEM((LANES, tn), F32), pltpu.VMEM((tn, LANES), F32)]


def _parts(dtype) -> int:
    return 3 if jnp.dtype(dtype) == jnp.dtype(BF16) else 1


def _dphi_rows(n, dtype) -> int:
    """Rows of the kernel's ``dphi`` a stream: ``phi``'s columns for each
    part of the logits' gradient, in whole tiles of the left side."""
    return -(-_parts(dtype) * coefficient_rows(n) // ROWS) * ROWS


def _phi_forward(phi, n, C, dtype):
    """``phi [n C, k]`` as the forward product's right side ``[n, C,
    128]``: for bfloat16 streams its three bfloat16 parts side by side."""
    phi = phi.astype(F32).reshape(n, C, -1)
    if _parts(dtype) == 3:
        phi = jnp.concatenate(_split3(phi), axis=-1).astype(BF16)
    return jnp.pad(phi, ((0, 0), (0, 0), (0, LANES - phi.shape[-1])))


def _phi_backward(phi, n, C, dtype):
    """``phi`` transposed ``[n, 128, C]`` for the product that takes the
    logits' gradient back to the streams: rows ``[p1; p2; p1]`` for
    bfloat16 streams (see the kernel)."""
    phit = phi.astype(F32).reshape(n, C, -1).transpose(0, 2, 1)
    if _parts(dtype) == 3:
        p1, p2, _ = _split3(phit)
        phit = jnp.concatenate([p1, p2, p1], axis=1).astype(BF16)
    return jnp.pad(phit, ((0, 0), (0, LANES - phit.shape[1]), (0, 0)))


def _scales(b, alpha, n):
    """``alpha``'s three scales a coefficient row, and ``b``, ``[k, 1]``."""
    a = alpha.astype(F32)
    a = jnp.concatenate([
        jnp.broadcast_to(a[0], (n,)), jnp.broadcast_to(a[1], (n,)),
        jnp.broadcast_to(a[2], (n * n,)),
    ])
    return a[:, None], b.astype(F32)[:, None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def read(streams, phi, b, alpha, norm_eps, sinkhorn_iters, eps, res_clamp):
    """The read side of a sublayer. ``streams [n, N, C]``, ``phi [n C, n^2
    + 2n]``, ``b [n^2 + 2n]``, ``alpha`` (three scales). Returns ``h [N,
    C]`` float32, the streams (unchanged: what :func:`write` takes them
    from, so that the remix's part of their gradient comes back here and
    is added inside this side's backward pass), the coefficients ``[n^2 +
    2n, N]`` (``pre``, ``post``, the rows of ``res``) and the mixing's
    three counters."""
    return _read_fwd(streams, phi, b, alpha, norm_eps, sinkhorn_iters, eps,
                     res_clamp)[0]


# Each rule below is a jit of its own: a step holds some twenty of these
# passes (a sublayer's, a scan's body traced twice, a block's rebuild) in
# four shapes, and a kernel's body is long (the width's lane tiles and the
# Sinkhorn iterations unrolled). Under jit one shape is traced once a
# process and lowered once a program; traced in line, every pass was, and
# the step's lowering took three times as long (PERF.md, Findings "PR 35").
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _read_fwd(streams, phi, b, alpha, norm_eps, sinkhorn_iters, eps,
              res_clamp):
    n, N, C = streams.shape
    k, tn = coefficient_rows(n), token_tile(n, N, C)
    spec = _specs(n, tn, C)
    a, bias = _scales(b, alpha, n)
    with jax.named_scope("moolib.lm.hc_mix"):
        h, coef, m, stat = _call(
            functools.partial(
                _read_kernel, n=n, parts=_parts(streams.dtype),
                norm_eps=norm_eps, iters=sinkhorn_iters, eps=eps,
                clamp=res_clamp),
            grid=(N // tn,),
            in_specs=[
                spec["streams"],
                pl.BlockSpec((n, C, LANES), lambda t: (0, 0, 0)),
                spec["scale"], spec["scale"],
            ],
            out_specs=[spec["width"], spec["rows"], spec["rows"],
                       spec["stat"]],
            out_shape=[
                jax.ShapeDtypeStruct((N, C), F32),
                jax.ShapeDtypeStruct((k, N), F32),
                jax.ShapeDtypeStruct((k, N), F32),
                jax.ShapeDtypeStruct((8, N), F32),
            ],
            scratch_shapes=[pltpu.VMEM((tn, LANES), F32)]
            + _transpose_scratch(tn),
        )(streams, _phi_forward(phi, n, C, streams.dtype), a, bias)
        counters = {
            "hc_row_sum_gap": jnp.max(stat[1]),
            "hc_col_sum_gap": jnp.max(stat[2]),
            "hc_res_clamped": jnp.sum(stat[3]),
        }
    return (h, streams, coef, counters), (streams, phi, b, alpha, m, stat)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _read_bwd(norm_eps, sinkhorn_iters, eps, res_clamp, kept, cotangents):
    streams, phi, b, alpha, m, stat = kept
    dh, dxa, dcoef, _ = cotangents
    n, N, C = streams.shape
    k, tn = coefficient_rows(n), token_tile(n, N, C)
    parts, rows = _parts(streams.dtype), _dphi_rows(n, streams.dtype)
    spec = _specs(n, tn, C)
    a, bias = _scales(b, alpha, n)
    with jax.named_scope("moolib.lm.hc_mix"):
        dx, dphi, dz = _call(
            functools.partial(
                _read_bwd_kernel, n=n, parts=parts, norm_eps=norm_eps,
                iters=sinkhorn_iters, eps=eps, clamp=res_clamp),
            grid=(N // tn,),
            in_specs=[
                spec["width"], spec["streams"], spec["streams"],
                spec["rows"], spec["rows"], spec["stat"],
                pl.BlockSpec((n, LANES, C), lambda t: (0, 0, 0)),
                spec["scale"], spec["scale"],
            ],
            out_specs=[
                spec["streams"],
                pl.BlockSpec((n, rows, C), lambda t: (0, 0, 0)),
                spec["rows"],
            ],
            out_shape=[
                jax.ShapeDtypeStruct((n, N, C), streams.dtype),
                jax.ShapeDtypeStruct((n, rows, C), F32),
                jax.ShapeDtypeStruct((k, N), F32),
            ],
            scratch_shapes=[pltpu.VMEM((n, tn, LANES), F32)]
            + _transpose_scratch(tn) + [pltpu.VMEM((tn, C), F32)],
            sequential=True,  # dphi is summed over the tiles in place
        )(dh.astype(F32), streams, dxa.astype(streams.dtype),
          dcoef.astype(F32), m, stat, _phi_backward(phi, n, C, streams.dtype),
          a, bias)
        dphi = dphi[:, :parts * k].reshape(n, parts, k, C).sum(axis=1)
        dphi = dphi.transpose(0, 2, 1).reshape(n * C, k)
        ms = m * _inv_rms(stat[0:1], n, C, norm_eps)
        scaled = jnp.sum(dz * ms, axis=1)
        dalpha = jnp.stack([
            jnp.sum(scaled[:n]), jnp.sum(scaled[n:2 * n]),
            jnp.sum(scaled[2 * n:]),
        ])
        db = jnp.sum(dz, axis=1)
    return (dx, dphi.astype(phi.dtype), db.astype(b.dtype),
            dalpha.astype(alpha.dtype))


read.defvjp(_read_fwd, _read_bwd)


@jax.custom_vjp
def write(streams, coef, y):
    """The write side: ``X'[i] = sum_j res[i, j] X[j] + post[i] y`` from
    the streams :func:`read` handed on, its coefficients and the sublayer's
    output ``y [N, C]``, in the streams' dtype."""
    return _write_fwd(streams, coef, y)[0]


@jax.jit
def _write_fwd(streams, coef, y):
    n, N, C = streams.shape
    tn = token_tile(n, N, C)
    spec = _specs(n, tn, C)
    with jax.named_scope("moolib.lm.hc_post"):
        out = _call(
            functools.partial(_write_kernel, n=n),
            grid=(N // tn,),
            in_specs=[spec["streams"], spec["width"], spec["rows"]],
            out_specs=spec["streams"],
            out_shape=jax.ShapeDtypeStruct(streams.shape, streams.dtype),
            scratch_shapes=_transpose_scratch(tn),
        )(streams, y, coef)
    return out, (streams, coef, y)


@jax.jit
def _write_bwd(kept, g):
    streams, coef, y = kept
    n, N, C = streams.shape
    k, tn = coefficient_rows(n), token_tile(n, N, C)
    spec = _specs(n, tn, C)
    with jax.named_scope("moolib.lm.hc_post"):
        dx, dy, dcoef = _call(
            functools.partial(_write_bwd_kernel, n=n),
            grid=(N // tn,),
            in_specs=[spec["streams"], spec["streams"], spec["width"],
                      spec["rows"]],
            out_specs=[spec["streams"], spec["width"], spec["rows"]],
            out_shape=[
                jax.ShapeDtypeStruct(streams.shape, streams.dtype),
                jax.ShapeDtypeStruct(y.shape, y.dtype),
                jax.ShapeDtypeStruct((k, N), F32),
            ],
            scratch_shapes=_transpose_scratch(tn)
            + [pltpu.VMEM((n * n + n, tn, LANES), F32)],
        )(g.astype(streams.dtype), streams, y, coef)
    return dx, dcoef.astype(coef.dtype), dy


write.defvjp(_write_fwd, _write_bwd)
