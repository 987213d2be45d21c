"""The five passes over the residual streams that ``blocks.hyper_residual``
lowers to on a TPU: each reads the streams as they lie, ``(N, n*D)`` in
their own dtype (stream ``j`` of a token is columns ``j*D .. (j+1)*D``),
a tile of whole tokens at a time, widens to float32 in the tile and
writes its wide results once, rounded.  No float32 copy of the streams
reaches HBM.

The small per-token maps travel as ``(N, 128)`` float32 arrays, one map
entry a lane ("lanes" below): ``H_pre[i]`` and ``H_post[i]`` in lane
``i``, ``H_res[i, j]`` in lane ``i*n + j``.  Nothing of the maps'
arithmetic is here but the one line that ``pre_fwd`` is handed
(``pre_map``), so that the projection, the norm and the pre-mix share
one read; Sinkhorn and the rest stay with ``blocks``.

Every pass is a ``pallas_call`` with a grid over token tiles where the
program is lowered for a TPU, and the same sums in plain ``jax.numpy``
(the ``_plain_*`` function beside it, which is also what the pass
computes, said briefly) where it is lowered for anything else;
``interpret=True`` runs the kernel through the Pallas interpreter (the
tests).  The choice sits round each pass alone and nothing
differentiates through it: the backward passes are passes too.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# what one tile of the streams may take of VMEM, in their own dtype: the
# widest pass holds three such tiles, each double-buffered
_TILE_BYTES = 4 << 20
_VMEM_SCRATCH = 16 << 20


def token_tile(n_tokens: int, width: int, itemsize: int):
    """Tokens a tile: the most of 256..16 that divide ``n_tokens`` and
    keep a tile of ``width`` values under ``_TILE_BYTES``; None where
    none does."""
    return next((t for t in (256, 128, 64, 32, 16)
                 if n_tokens % t == 0
                 and t * width * itemsize <= _TILE_BYTES), None)


def _chunk(d: int) -> int:
    """Columns a step of a pass's inner loop: 512 keeps a float32 chunk
    of 128 tokens at 64 registers' worth."""
    return next(c for c in (512, 256, 128) if d % c == 0)


def _row_sum(v):
    """Sum over lanes, kept as a ``(tokens, 1)`` column (``lax`` itself:
    a pass holds scores of these, and ``jnp``'s wrappers cost a start-up
    milliseconds each to trace)."""
    return lax.expand_dims(lax.reduce_sum(v, (1,)), (1,))


def _lane(v, k: int):
    """Lane ``k`` of a lanes tile as a ``(tokens, 1)`` column: a masked
    lane reduction, which lowers wherever the lane lies."""
    lanes = lax.broadcasted_iota(jnp.int32, v.shape, 1)
    return _row_sum(lax.select(lax.eq(lanes, jnp.int32(k)), v,
                               lax.full_like(v, 0)))


def _lane_grid(v, n: int):
    """Lanes ``i*n + j`` of a lanes tile as ``n`` rows of ``n`` columns."""
    return [[_lane(v, i * n + j) for j in range(n)] for i in range(n)]


def _to_lanes(cols, rows: int):
    """``(tokens, 1)`` columns back into one lanes tile, column ``k``
    into lane ``k``."""
    lanes = lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    out = lax.full((rows, LANES), 0, jnp.float32)
    for k, col in enumerate(cols):
        out = lax.select(lax.eq(lanes, jnp.int32(k)),
                         lax.broadcast_in_dim(col, out.shape, (0, 1)), out)
    return out


def _fold(prod):
    """A ``(tokens, chunk)`` product summed over its 128-lane groups: the
    partial a reduction over columns carries from chunk to chunk, so
    that only the end of a pass reduces across lanes."""
    groups = [lax.slice_in_dim(prod, s, s + LANES, axis=1)
              for s in range(0, prod.shape[1], LANES)]
    return functools.reduce(lax.add, groups)


def _cols(ref, j: int, d: int, c, dc: int):
    """Chunk ``c`` of stream ``j``, float32."""
    start = pl.multiple_of(j * d + c * dc, LANES)
    return ref[:, pl.ds(start, dc)].astype(jnp.float32)


def _call(kernel, name, n_tokens, tile, ins, outs, interpret, carries=False):
    """``ins``: (array, how) pairs, ``outs``: (shape, dtype, how).  How an
    array meets the grid over token tiles: ``tokens`` (N, c) a tile of
    rows with their whole width, ``token_columns`` (r, N) the same tile
    as columns, ``whole`` all of it at every step.  ``carries``: an
    output is summed over the steps, so they run in order."""
    def block(shape, how):
        if how == "tokens":
            return (tile, shape[1]), lambda i: (i, 0)
        if how == "token_columns":
            return (shape[0], tile), lambda i: (0, i)
        return tuple(shape), lambda i: (0, 0)

    arrays = [(a.shape, a.dtype, how) for a, how in ins] + list(outs)
    # every block twice (the pipeline's two buffers) and room for what a
    # pass keeps of a chunk in float32
    vmem = _VMEM_SCRATCH + 2 * sum(
        math.prod(block(shape, how)[0]) * jnp.dtype(dt).itemsize
        for shape, dt, how in arrays)
    return tuple(pl.pallas_call(
        kernel, name=name, grid=(n_tokens // tile,),
        in_specs=[pl.BlockSpec(*block(a.shape, how)) for a, how in ins],
        out_specs=[pl.BlockSpec(*block(shape, how))
                   for shape, _, how in outs],
        out_shape=[jax.ShapeDtypeStruct(shape, dt) for shape, dt, _ in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if carries else "parallel",),
            vmem_limit_bytes=vmem),
        interpret=interpret,
    )(*(a for a, _ in ins)))


def _by_platform(plain):
    """``kernel(*arrays, interpret=, **static)`` -> the same call, lowered
    to the kernel for a TPU (or run by the interpreter) and to ``plain``
    for any other platform."""
    def wrap(kernel):
        @functools.wraps(kernel)
        def run(*arrays, interpret=False, **static):
            if interpret:
                return kernel(*arrays, interpret=True, **static)
            return lax.platform_dependent(
                *arrays, tpu=functools.partial(kernel, **static),
                default=functools.partial(plain, **static))
        return run
    return wrap


def _streams(x, n: int):
    """(N, n*D) -> (N, n, D) float32."""
    return x.astype(jnp.float32).reshape(x.shape[0], n, -1)


def _pad_lanes(a):
    return jnp.pad(a, ((0, 0), (0, LANES - a.shape[1])))


_HIGHEST = lax.Precision.HIGHEST


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """float32-grade product: bf16 operands are exact parts already (one
    pass each), float32 operands take the full-precision passes."""
    precision = None if a.dtype == jnp.bfloat16 else lax.Precision.HIGHEST
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32,
                           precision=precision)


def _dot_t(a, b):
    """``a @ b.T``."""
    return _dot(a, b, (((1,), (1,)), ((), ())))


# ---------------------------------------------------------------------
# before the sublayer
# ---------------------------------------------------------------------
def _plain_pre_fwd(x, w_cols, fold, a_row, b_row, *, n, eps, pre_map, tile):
    xf = x.astype(jnp.float32)
    u = jnp.dot(jnp.dot(xf, w_cols.astype(jnp.float32), precision=_HIGHEST),
                fold, precision=_HIGHEST)
    ms = jnp.mean(xf * xf, axis=1, keepdims=True)
    pre = pre_map(a_row, u * lax.rsqrt(ms + eps), b_row)
    mixed = jnp.einsum("tn,tnd->td", pre[:, :n], _streams(x, n))
    return u, jnp.broadcast_to(ms, u.shape), mixed.astype(x.dtype)


@_by_platform(_plain_pre_fwd)
def pre_fwd(x, w_cols, fold, a_row, b_row, *, n, eps, pre_map, tile,
            interpret=False):
    """``x`` (N, n*D) -> the raw projection ``x @ W`` (lanes), the mean
    square of a token's ``n*D`` values (every lane), and ``mixed`` (N, D)
    = sum_i ``pre_map(a_row, u * rsqrt(ms + eps), b_row)[i]`` x[i],
    rounded once.  ``w_cols`` (n*D, 128) holds ``W`` in ``x``'s dtype:
    for bf16 its three bf16 parts side by side, which ``fold`` (128, 128)
    adds up (the identity for float32)."""
    n_tokens, width = x.shape
    d = width // n
    dc = _chunk(d)

    def kernel(x_ref, w_ref, fold_ref, a_ref, b_ref, u_ref, ms_ref, mix_ref):
        def project(c, carry):
            acc, gram = carry
            start = pl.multiple_of(c * dc, LANES)
            xc = x_ref[:, pl.ds(start, dc)]
            return (acc + _dot(xc, w_ref[pl.ds(start, dc), :]),
                    gram + _dot_t(xc, xc))

        # the squares are summed on the MXU too, as the diagonal of the
        # tile's Gram matrix: exact products of the streams' own values,
        # float32 sums, and no widening pass over the tile for them
        acc, gram = lax.fori_loop(
            0, width // dc, project,
            (jnp.zeros((tile, LANES), jnp.float32),
             jnp.zeros((tile, tile), jnp.float32)))
        u = jnp.dot(acc, fold_ref[...], preferred_element_type=jnp.float32,
                    precision=lax.Precision.HIGHEST)
        diagonal = lax.broadcasted_iota(jnp.int32, gram.shape, 0) \
            == lax.broadcasted_iota(jnp.int32, gram.shape, 1)
        ms = _row_sum(lax.select(diagonal, gram, lax.full_like(gram, 0))) \
            / width
        u_ref[...] = u
        ms_ref[...] = jnp.broadcast_to(ms, (tile, LANES))
        pre = pre_map(a_ref[...], u * lax.rsqrt(ms + eps), b_ref[...])
        h = [_lane(pre, i) for i in range(n)]

        def mix(c, _):
            out = h[0] * _cols(x_ref, 0, d, c, dc)
            for i in range(1, n):
                out = out + h[i] * _cols(x_ref, i, d, c, dc)
            mix_ref[:, pl.ds(pl.multiple_of(c * dc, LANES), dc)] = \
                out.astype(mix_ref.dtype)
            return 0

        lax.fori_loop(0, d // dc, mix, 0)

    return _call(kernel, "mhc_pre_fwd", n_tokens, tile,
                 [(x, "tokens"), (w_cols, "whole"), (fold, "whole"),
                  (a_row, "whole"), (b_row, "whole")],
                 [((n_tokens, LANES), jnp.float32, "tokens"),
                  ((n_tokens, LANES), jnp.float32, "tokens"),
                  ((n_tokens, d), x.dtype, "tokens")], interpret)


def _plain_pre_bwd_maps(x, g_mixed, *, n, tile):
    return _pad_lanes(jnp.einsum("td,tnd->tn", g_mixed.astype(jnp.float32),
                                 _streams(x, n)))


@_by_platform(_plain_pre_bwd_maps)
def pre_bwd_maps(x, g_mixed, *, n, tile, interpret=False):
    """What the pre-mix hands back to ``H_pre``: lane ``i`` holds
    sum_d ``g_mixed[d] x[i, d]``."""
    n_tokens, width = x.shape
    d = width // n
    dc = _chunk(d)

    def kernel(x_ref, g_ref, out_ref):
        def body(c, accs):
            g = _cols(g_ref, 0, d, c, dc)
            return tuple(acc + _fold(g * _cols(x_ref, i, d, c, dc))
                         for i, acc in enumerate(accs))

        zero = jnp.zeros((tile, LANES), jnp.float32)
        accs = lax.fori_loop(0, d // dc, body, (zero,) * n)
        out_ref[...] = _to_lanes(
            [_row_sum(a) for a in accs], tile)

    return _call(kernel, "mhc_pre_bwd_maps", n_tokens, tile,
                 [(x, "tokens"), (g_mixed, "tokens")],
                 [((n_tokens, LANES), jnp.float32, "tokens")], interpret)[0]


def _plain_pre_bwd_streams(x, g_out, g_mixed, du_cols, du_rows, wt_cols, cx,
                           pre, res, *, n, tile):
    f32 = jnp.float32
    xf = x.astype(f32)
    dx = jnp.dot(du_cols.astype(f32), wt_cols.astype(f32),
                 precision=_HIGHEST) + cx[:, :1] * xf
    dx = dx.reshape(_streams(x, n).shape) \
        + pre[:, :n, None] * g_mixed.astype(f32)[:, None, :] \
        + jnp.einsum("tij,tid->tjd", res[:, :n * n].reshape(-1, n, n),
                     _streams(g_out, n))
    return (dx.reshape(x.shape).astype(x.dtype),
            jnp.dot(du_rows.astype(f32), xf, precision=_HIGHEST))


@_by_platform(_plain_pre_bwd_streams)
def pre_bwd_streams(x, g_out, g_mixed, du_cols, du_rows, wt_cols, cx, pre,
                    res, *, n, tile, interpret=False):
    """The streams' whole gradient, its three parts summed in float32
    and rounded once: through the maps (``du_cols @ wt_cols`` and the
    norm's ``cx * x``), through the pre-mix (``pre[j] g_mixed``) and
    through the post-mix (sum_i ``res[i, j] g_out[i]``); and, summed over
    the token tiles on the way, ``du_rows @ x`` (128, n*D), the
    projection's gradient transposed.  ``du_cols`` (N, 128), ``du_rows``
    (128, N) and ``wt_cols`` (128, n*D) are in ``x``'s dtype: for bf16
    the parts of ``dU`` and of ``W^T``, paired along the 128."""
    n_tokens, width = x.shape
    d = width // n
    dc = _chunk(d)

    def kernel(x_ref, go_ref, gm_ref, du_ref, dur_ref, wt_ref, cx_ref,
               pre_ref, res_ref, dx_ref, dw_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            dw_ref[...] = jnp.zeros_like(dw_ref)

        du, du_rows_ = du_ref[...], dur_ref[...]
        cx_col = _lane(cx_ref[...], 0)
        h_pre = [_lane(pre_ref[...], j) for j in range(n)]
        h_res = _lane_grid(res_ref[...], n)

        def body(c, _):
            gm = _cols(gm_ref, 0, d, c, dc)
            go = [_cols(go_ref, i, d, c, dc) for i in range(n)]
            for j in range(n):
                start = pl.multiple_of(j * d + c * dc, LANES)
                xc = x_ref[:, pl.ds(start, dc)]
                dw_ref[:, pl.ds(start, dc)] += _dot(du_rows_, xc)
                dx = _dot(du, wt_ref[:, pl.ds(start, dc)]) \
                    + cx_col * xc.astype(jnp.float32) + h_pre[j] * gm
                for i in range(n):
                    dx = dx + h_res[i][j] * go[i]
                dx_ref[:, pl.ds(start, dc)] = dx.astype(dx_ref.dtype)
            return 0

        lax.fori_loop(0, d // dc, body, 0)

    return _call(kernel, "mhc_pre_bwd_streams", n_tokens, tile,
                 [(x, "tokens"), (g_out, "tokens"), (g_mixed, "tokens"),
                  (du_cols, "tokens"), (du_rows, "token_columns"),
                  (wt_cols, "whole"), (cx, "tokens"), (pre, "tokens"),
                  (res, "tokens")],
                 [((n_tokens, width), x.dtype, "tokens"),
                  ((LANES, width), jnp.float32, "whole")],
                 interpret, carries=True)


# ---------------------------------------------------------------------
# after the sublayer
# ---------------------------------------------------------------------
def _plain_post_fwd(x, y, res, post, *, n, tile):
    out = jnp.einsum("tij,tjd->tid", res[:, :n * n].reshape(-1, n, n),
                     _streams(x, n)) \
        + post[:, :n, None] * y.astype(jnp.float32)[:, None, :]
    return out.reshape(x.shape).astype(x.dtype)


@_by_platform(_plain_post_fwd)
def post_fwd(x, y, res, post, *, n, tile, interpret=False):
    """``out[i] = sum_j res[i, j] x[j] + post[i] y``, rounded once."""
    n_tokens, width = x.shape
    d = width // n
    dc = _chunk(d)

    def kernel(x_ref, y_ref, res_ref, post_ref, out_ref):
        h_post = [_lane(post_ref[...], i) for i in range(n)]
        h_res = _lane_grid(res_ref[...], n)

        def body(c, _):
            yc = _cols(y_ref, 0, d, c, dc)
            xc = [_cols(x_ref, j, d, c, dc) for j in range(n)]
            for i in range(n):
                out = h_post[i] * yc
                for j in range(n):
                    out = out + h_res[i][j] * xc[j]
                start = pl.multiple_of(i * d + c * dc, LANES)
                out_ref[:, pl.ds(start, dc)] = out.astype(out_ref.dtype)
            return 0

        lax.fori_loop(0, d // dc, body, 0)

    return _call(kernel, "mhc_post_fwd", n_tokens, tile,
                 [(x, "tokens"), (y, "tokens"), (res, "tokens"),
                  (post, "tokens")],
                 [((n_tokens, width), x.dtype, "tokens")], interpret)[0]


def _plain_post_bwd(x, y, g_out, post, *, n, tile):
    g = _streams(g_out, n)
    d_res = jnp.einsum("tid,tjd->tij", g, _streams(x, n))
    return (jnp.einsum("ti,tid->td", post[:, :n], g).astype(y.dtype),
            _pad_lanes(d_res.reshape(x.shape[0], n * n)),
            _pad_lanes(jnp.einsum("tid,td->ti", g, y.astype(jnp.float32))))


@_by_platform(_plain_post_bwd)
def post_bwd(x, y, g_out, post, *, n, tile, interpret=False):
    """From the new streams' cotangent ``g_out``: ``dy`` = sum_i
    ``post[i] g_out[i]`` (rounded once), ``d_res[i, j]`` = sum_d
    ``g_out[i, d] x[j, d]`` and ``d_post[i]`` = sum_d ``g_out[i, d]
    y[d]`` (lanes)."""
    n_tokens, width = x.shape
    d = width // n
    dc = _chunk(d)

    def kernel(x_ref, y_ref, go_ref, post_ref, dy_ref, dres_ref, dpost_ref):
        h_post = [_lane(post_ref[...], i) for i in range(n)]

        def body(c, accs):
            yc = _cols(y_ref, 0, d, c, dc)
            xc = [_cols(x_ref, j, d, c, dc) for j in range(n)]
            go = [_cols(go_ref, i, d, c, dc) for i in range(n)]
            dy = h_post[0] * go[0]
            for i in range(1, n):
                dy = dy + h_post[i] * go[i]
            dy_ref[:, pl.ds(pl.multiple_of(c * dc, LANES), dc)] = \
                dy.astype(dy_ref.dtype)
            new = [accs[i * n + j] + _fold(go[i] * xc[j])
                   for i in range(n) for j in range(n)]
            new += [accs[n * n + i] + _fold(go[i] * yc) for i in range(n)]
            return tuple(new)

        zero = jnp.zeros((tile, LANES), jnp.float32)
        accs = lax.fori_loop(0, d // dc, body, (zero,) * (n * n + n))
        cols = [_row_sum(a) for a in accs]
        dres_ref[...] = _to_lanes(cols[:n * n], tile)
        dpost_ref[...] = _to_lanes(cols[n * n:], tile)

    return _call(kernel, "mhc_post_bwd", n_tokens, tile,
                 [(x, "tokens"), (y, "tokens"), (g_out, "tokens"),
                  (post, "tokens")],
                 [((n_tokens, d), x.dtype, "tokens"),
                  ((n_tokens, LANES), jnp.float32, "tokens"),
                  ((n_tokens, LANES), jnp.float32, "tokens")], interpret)
