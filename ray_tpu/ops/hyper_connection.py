"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880): a residual
stream of n LANES mixed per token round every sublayer, as Pallas passes
over tiles of rows with backward passes of their own, and the XLA
formulation elsewhere.

The stream is x [b, s, n d], a token's n lanes X_0 .. X_{n-1} (each d wide)
side by side on the last axis: vec(X), as the norm and the 2 n + n^2 wide
product read it (a [b, s, n, d] array would lie in tiles of 16 x 128 with 12
of the 16 rows padding).  Round a sublayer F with its own leaves w_hc [n d,
2 n + n^2], scale [3], base [2 n + n^2] (all float32):

    r    = rsqrt(mean(x^2) + norm_eps);  m = (x w_hc) r          (float32)
    pre  = sigmoid(scale_0 m[:n] + base[:n]) + eps
    post = 2 sigmoid(scale_1 m[n:2n] + base[n:2n])
    C    = clip(scale_2 m[2n:] + base[2n:], lo, hi) as [n, n], row j col i
    comb = Sinkhorn(C): M = softmax of each row + eps; M /= column sums +
           eps; then `iters` - 1 times M /= row sums + eps, M /= column
           sums + eps
    u    = sum_i pre_i X_i;   y = F(RMSNorm(u));
    X'_j = post_j y + sum_i comb_ji X_i

  hc_pre(x, w_hc, scale, base, hc) -> (u [b, s, d] in x's dtype, mix, x)
  hc_post(x, y, mix, hc)           -> x' like x
  hc_collapse(x, w_head [n d, n], scale_h [1], base_h [n], hc) -> [b, s, d]:
      sum_i (sigmoid(scale_h m + base_h) + eps)_i X_i behind the last layer

`mix` [b, s, 128] float32 is a token's `pre | post | comb` (row-major, comb_ji
at 2 n + j n + i) in its first 2 n + n^2 columns and zeros behind
(`mix_parts` cuts it): one lane-dense row a token, 512 B beside the
stream's 2 n d, which both calls' kernels read with tokens on the SUBLANES,
where the lanes' products want a token's numbers.

The kernels.  Grid (batch, row tiles); a program holds a tile of `ROWS`
tokens by the whole stream, float32 inside, bfloat16 across HBM.
  `hc_pre_fwd`   reads the tile ONCE: the product as W [128, n d] (w_hc's
      three bfloat16 parts, rows 0 / 32 / 64 on) against the tile on the
      MXU with TOKENS ON THE LANE AXIS of the result; the norm's sum; the
      sigmoids and the rounds on 2 n + n^2 numbers a token as [n, rows]
      blocks; one transpose to the token-a-sublane order; the weighted sum.
  `hc_post_fwd`  reads x, y, mix, writes x'.
  `hc_post_bwd`  reads x, y, mix and dx': dy, dx (the lanes' part, written
      over dx') and d post, d comb as 20 sums over d, written into mix's
      columns.
  `hc_pre_bwd`   reads x, du and d mix: the forward's m, r and the rounds
      made AGAIN from C (nothing of the rounds is kept), pulled back by
      `jax.vjp` of the same arithmetic traced inside the kernel;
      dx once (through u, through the norm, through the product, and what
      `hc_post_bwd` wrote, over which it is written: `hc_pre` hands x on),
      dW and the cotangents of scale and base added into blocks that stay
      resident over the grid.
Off the kernels' shapes (a stream that is not bfloat16, d no multiple of 128, rows no multiple of `ROWS`,
2 n + n^2 over 32, a mesh of several devices, neither a TPU nor the
interpreter) the XLA formulation (`_xla_pre`, `_xla_post`: by whole arrays,
differentiated by JAX).  `*_reference` is the
equations above a step at a time on [.., n, n] matrices, which the tests
hold both to.  `dispatch.taken()["hyper_connection"]` says which was traced.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ray_tpu.ops import dispatch

F32 = jnp.float32
MIX_COLUMNS = 128       # a token's row of `mix`
PART_ROWS = 32          # rows of W a bfloat16 part of w_hc takes
ROWS = 128              # tokens a program


class HC(NamedTuple):
    """The lanes' settings, the published keys' values in order: `hc_mult`,
    `hc_sinkhorn_iters`, `hc_eps`, `rms_norm_eps`, `mhc_h_res_clamp_min`,
    `mhc_h_res_clamp_max`."""
    n: int
    iters: int = 20
    eps: float = 1e-6
    norm_eps: float = 1e-6
    clamp_min: float = -30.0
    clamp_max: float = 30.0

    @property
    def width(self) -> int:
        return 2 * self.n + self.n * self.n


def mix_parts(mix, n: int):
    """mix [.., 128] -> (pre [.., n], post [.., n], comb [.., n, n])."""
    return (mix[..., :n], mix[..., n:2 * n],
            mix[..., 2 * n:2 * n + n * n].reshape(*mix.shape[:-1], n, n))


# ---------------------------------------------------------------------------
# The equations, a step at a time
# ---------------------------------------------------------------------------

def sinkhorn_reference(c, iters: int, eps: float):
    """c [.., n, n] -> the projected matrix, as the header spells it."""
    m = jax.nn.softmax(c, axis=-1) + eps
    m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    for _ in range(iters - 1):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def hc_mix_reference(x, w_hc, scale, base, hc: HC):
    """(pre [.., n], post [.., n], comb [.., n, n]) float32."""
    n = hc.n
    xf = x.astype(F32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + hc.norm_eps)
    m = jnp.einsum("...k,kw->...w", xf, w_hc.astype(F32),
                   precision=jax.lax.Precision.HIGHEST) * r
    pre = jax.nn.sigmoid(scale[0] * m[..., :n] + base[:n]) + hc.eps
    post = 2.0 * jax.nn.sigmoid(scale[1] * m[..., n:2 * n] + base[n:2 * n])
    c = jnp.clip(scale[2] * m[..., 2 * n:] + base[2 * n:], hc.clamp_min,
                 hc.clamp_max).reshape(*m.shape[:-1], n, n)
    return pre, post, sinkhorn_reference(c, hc.iters, hc.eps)


def _as_mix(pre, post, comb):
    flat = jnp.concatenate(
        [pre, post, comb.reshape(*comb.shape[:-2], -1)], axis=-1)
    return jnp.pad(flat, [(0, 0)] * (flat.ndim - 1)
                   + [(0, MIX_COLUMNS - flat.shape[-1])])


def hc_pre_reference(x, w_hc, scale, base, hc: HC, out_dtype=None):
    pre, post, comb = hc_mix_reference(x, w_hc, scale, base, hc)
    lanes = x.astype(F32).reshape(*x.shape[:-1], hc.n, -1)
    u = jnp.einsum("...i,...id->...d", pre, lanes)
    return u.astype(out_dtype or x.dtype), _as_mix(pre, post, comb)


def hc_post_reference(x, y, mix, hc: HC, out_dtype=None):
    _, post, comb = mix_parts(mix, hc.n)
    lanes = x.astype(F32).reshape(*x.shape[:-1], hc.n, -1)
    out = post[..., None] * y.astype(F32)[..., None, :] \
        + jnp.einsum("...ji,...id->...jd", comb, lanes)
    return out.reshape(x.shape).astype(out_dtype or x.dtype)


# ---------------------------------------------------------------------------
# The per-token arithmetic with TOKENS ON THE LANE AXIS: what the kernels
# trace on [n, rows] blocks
# ---------------------------------------------------------------------------

def _sigmoid(z):        # one tanh
    return 0.5 * jnp.tanh(0.5 * z) + 0.5


def _mix_rows(z, hc: HC):
    """z: scale x m + base as its 2 + n blocks of n numbers (tokens on the
    last axis, a block's numbers on the one before): pre's, post's and C's
    rows -> the blocks of pre | post | comb.  A row's sum is a sum over a
    block's numbers, a column's the blocks added."""
    n, eps = hc.n, hc.eps
    pre, post = _sigmoid(z[0]) + eps, 2.0 * _sigmoid(z[1])

    def total(row):
        return jnp.sum(row, axis=-2, keepdims=True)

    def by_rows(m):
        return [row / (total(row) + eps) for row in m]

    def by_columns(m):
        sums = sum(m) + eps
        return [row / sums for row in m]

    m = []
    for row in z[2:]:       # softmax of a row + eps
        row = jnp.clip(row, hc.clamp_min, hc.clamp_max)
        e = jnp.exp(row - jax.lax.stop_gradient(
            jnp.max(row, axis=-2, keepdims=True)))
        m.append(e / total(e) + eps)
    m = by_columns(m)
    for _ in range(hc.iters - 1):
        m = by_columns(by_rows(m))
    return [pre, post, *m]


def _scale_rows(scale, hc: HC):
    """scale [3] -> [2 n + n^2]: each number's scale."""
    n = hc.n
    return jnp.concatenate([jnp.broadcast_to(scale[0], (n,)),
                            jnp.broadcast_to(scale[1], (n,)),
                            jnp.broadcast_to(scale[2], (n * n,))]).astype(F32)


def _xla_pre(x, w_hc, scale, base, hc: HC, out_dtype=None):
    """By whole arrays, the matrices as [.., n, n] (the rows' arithmetic of
    `_mix_rows` spelt a number at a time is what the kernels trace; as XLA
    operations it is some thousand of them a call)."""
    n, eps = hc.n, hc.eps
    xf = x.astype(F32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + hc.norm_eps)
    m = jnp.einsum("...k,kw->...w", xf, w_hc.astype(F32),
                   precision=jax.lax.Precision.HIGHEST) * r
    z = _scale_rows(scale, hc) * m + base.astype(F32)
    pre = _sigmoid(z[..., :n]) + eps
    post = 2.0 * _sigmoid(z[..., n:2 * n])
    c = jnp.clip(z[..., 2 * n:], hc.clamp_min, hc.clamp_max)
    c = c.reshape(*c.shape[:-1], n, n)
    e = jnp.exp(c - jax.lax.stop_gradient(jnp.max(c, -1, keepdims=True)))
    comb = e / jnp.sum(e, -1, keepdims=True) + eps
    comb = comb / (jnp.sum(comb, -2, keepdims=True) + eps)
    for _ in range(hc.iters - 1):
        comb = comb / (jnp.sum(comb, -1, keepdims=True) + eps)
        comb = comb / (jnp.sum(comb, -2, keepdims=True) + eps)
    u = jnp.einsum("...i,...id->...d", pre, xf.reshape(*x.shape[:-1], n, -1))
    return u.astype(out_dtype or x.dtype), _as_mix(pre, post, comb)


def _xla_post(x, y, mix, hc: HC, out_dtype=None):
    n = hc.n
    lanes, yf = jnp.split(x.astype(F32), n, axis=-1), y.astype(F32)
    out = [mix[..., n + j, None] * yf + sum(
        mix[..., 2 * n + j * n + i, None] * lanes[i] for i in range(n))
        for j in range(n)]
    return jnp.concatenate(out, axis=-1).astype(out_dtype or x.dtype)


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))      # a [p, k] x [q, k] -> [p, q] product


def _plan(x, hc: HC):
    """The row tile, or None where the shapes are not the kernels'."""
    if x.ndim != 3 or x.shape[2] % hc.n or x.dtype != jnp.bfloat16:
        return None
    d = x.shape[2] // hc.n
    if d % 128 or x.shape[1] % ROWS or hc.width > PART_ROWS:
        return None
    return ROWS


def _compiler_params(semantics):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=100 << 20)


def _parts(w, parts: int):
    """w [k, width] float32 -> [128, k] bfloat16: w^T's `parts` bfloat16
    parts (w = part 0 + part 1 + ..), part p in rows 32 p on, zeros
    between."""
    out, rest = [], w.astype(F32).T
    for _ in range(parts):
        part = rest.astype(jnp.bfloat16)
        rest = rest - part.astype(F32)
        out.append(jnp.pad(part, ((0, PART_ROWS - part.shape[0]), (0, 0))))
    out = jnp.concatenate(out)
    return jnp.pad(out, ((0, MIX_COLUMNS - out.shape[0]), (0, 0)))


def _lanes(ref, n: int):
    """The n lanes' column slices of a [rows, n d] block."""
    d = ref.shape[1] // n
    return [slice(i * d, (i + 1) * d) for i in range(n)]


def _row_sum(x):
    return jnp.sum(x, axis=-1, keepdims=True)


def _column(block, k: int):
    """Column k of a [rows, 128] block: [rows, 1]."""
    return block[:, k:k + 1]


def _in_column(values):
    """{column: [rows, 1]} -> [rows, 128] with those columns, zeros
    elsewhere."""
    rows = next(iter(values.values())).shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, MIX_COLUMNS), 1)
    out = jnp.zeros((rows, MIX_COLUMNS), F32)
    for k, v in values.items():
        out = jnp.where(lane == k, v, out)
    return out


def _to_lanes(column):
    """[rows, 1] -> [1, rows]: a token's number from its sublane to its
    lane."""
    wide = jnp.broadcast_to(column, (column.shape[0], MIX_COLUMNS))
    return jnp.transpose(wide)[0:1]


def _product(w_ref, x_ref, width: int):
    """(x w_hc)^T [width, rows] float32 from W's three parts."""
    mm = jax.lax.dot_general(w_ref[...], x_ref[...], _NT,
                             preferred_element_type=F32)
    return (mm[0:width] + mm[PART_ROWS:PART_ROWS + width]
            + mm[2 * PART_ROWS:2 * PART_ROWS + width])


def _norm_scale(x_ref, hc: HC):
    """rsqrt(mean(x^2) + norm_eps) as [1, rows]."""
    total = None
    for cols in _lanes(x_ref, hc.n):
        lane = x_ref[:, cols].astype(F32)
        part = _row_sum(lane * lane)
        total = part if total is None else total + part
    return jax.lax.rsqrt(_to_lanes(total) * (1.0 / x_ref.shape[1])
                         + hc.norm_eps)


def _blocks_of(ref, n: int):
    """The 2 + n blocks of n rows of a [.., rows] scratch."""
    return [ref[k * n:(k + 1) * n, :] for k in range(2 + n)]


def _store_blocks(ref, blocks, n: int):
    for k, block in enumerate(blocks):
        ref[k * n:(k + 1) * n, :] = block


def _pre_fwd_kernel(x_ref, w_ref, scale_ref, base_ref, u_ref, mix_ref,
                    rows_ref, *, hc: HC):
    width = hc.width
    r = _norm_scale(x_ref, hc)
    rows_ref[...] = jnp.zeros(rows_ref.shape, F32)
    rows_ref[0:width, :] = (scale_ref[...] * (_product(w_ref, x_ref, width)
                                              * r) + base_ref[...])
    _store_blocks(rows_ref, _mix_rows(_blocks_of(rows_ref, hc.n), hc), hc.n)
    mix = jnp.transpose(rows_ref[...])              # [rows, 128]
    mix_ref[...] = mix
    u = None
    for i, cols in enumerate(_lanes(x_ref, hc.n)):
        term = _column(mix, i) * x_ref[:, cols].astype(F32)
        u = term if u is None else u + term
    u_ref[...] = u.astype(u_ref.dtype)


def _post_fwd_kernel(x_ref, y_ref, mix_ref, o_ref, *, hc: HC):
    n = hc.n
    mix, y = mix_ref[...], y_ref[...].astype(F32)
    lanes = _lanes(x_ref, n)
    for j in range(n):
        acc = _column(mix, n + j) * y
        for i in range(n):
            acc = acc + _column(mix, 2 * n + j * n + i) \
                * x_ref[:, lanes[i]].astype(F32)
        o_ref[:, lanes[j]] = acc.astype(o_ref.dtype)


def _post_bwd_kernel(x_ref, y_ref, mix_ref, g_ref, dx_ref, dy_ref, dmix_ref,
                     *, hc: HC):
    n = hc.n
    mix, y = mix_ref[...], y_ref[...].astype(F32)
    lanes = _lanes(x_ref, n)
    found, dy = {}, None
    for j in range(n):
        g = g_ref[:, lanes[j]].astype(F32)
        term = _column(mix, n + j) * g
        dy = term if dy is None else dy + term
        found[n + j] = _row_sum(g * y)
        for i in range(n):
            found[2 * n + j * n + i] = _row_sum(
                g * x_ref[:, lanes[i]].astype(F32))
    dy_ref[...] = dy.astype(dy_ref.dtype)
    for i in range(n):
        acc = None
        for j in range(n):
            term = _column(mix, 2 * n + j * n + i) \
                * g_ref[:, lanes[j]].astype(F32)
            acc = term if acc is None else acc + term
        dx_ref[:, lanes[i]] = acc.astype(dx_ref.dtype)
    dmix_ref[...] = _in_column(found)


def _pre_bwd_kernel(x_ref, w_ref, wb_ref, scale_ref, base_ref, du_ref,
                    dmix_ref, dpass_ref, dx_ref, dw_ref, dscale_ref,
                    dbase_ref, rows_ref, *, hc: HC):
    from jax.experimental import pallas as pl

    n, width = hc.n, hc.width
    lanes = _lanes(x_ref, n)

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, F32)
        dscale_ref[...] = jnp.zeros(dscale_ref.shape, F32)
        dbase_ref[...] = jnp.zeros(dbase_ref.shape, F32)

    # the forward again: m, r, z and the rounds, pulled back from C
    r = _norm_scale(x_ref, hc)
    m = _product(w_ref, x_ref, width)
    scale = scale_ref[...]
    rows_ref[...] = jnp.zeros(rows_ref.shape, F32)
    rows_ref[0:width, :] = scale * (m * r) + base_ref[...]
    out, pull = jax.vjp(lambda z: _mix_rows(z, hc), _blocks_of(rows_ref, n))
    # the cotangent of pre | post | comb, a token a lane: what came in and
    # u's part of pre's, d pre_i = <du, X_i>
    du = du_ref[...].astype(F32)
    g = dmix_ref[...] + _in_column({
        i: _row_sum(du * x_ref[:, cols].astype(F32))
        for i, cols in enumerate(lanes)})
    rows_ref[...] = jnp.transpose(g)
    dz, = pull(_blocks_of(rows_ref, n))
    pre = out[0]                        # for u's part of dx
    _store_blocks(rows_ref, dz, n)
    dz = rows_ref[0:width, :]
    dbase_ref[...] += dz
    dscale_ref[...] += dz * (m * r)
    dm = dz * scale * r                 # the product's cotangent
    dr = jnp.sum(dz * scale * m, axis=0, keepdims=True)
    # d mean-square -> x: dr (-1/2) r^3 / (n d), times 2 x
    coef = dr * (r * r * r) * (-1.0 / x_ref.shape[1])
    # dm's two bfloat16 parts against W's: hi hi + lo hi + hi lo
    hi = dm.astype(jnp.bfloat16).astype(F32)
    lo = dm - hi
    rows_ref[...] = jnp.zeros(rows_ref.shape, F32)
    rows_ref[0:width, :] = hi
    rows_ref[PART_ROWS:PART_ROWS + width, :] = lo
    rows_ref[2 * PART_ROWS:2 * PART_ROWS + width, :] = hi
    dm_parts = rows_ref[...]
    dw_ref[...] += jnp.dot(dm_parts.astype(jnp.bfloat16), x_ref[...],
                           preferred_element_type=F32)
    dm_rows = jnp.transpose(dm_parts).astype(jnp.bfloat16)     # [rows, 128]
    rows_ref[...] = jnp.zeros(rows_ref.shape, F32)
    rows_ref[0:n, :] = pre
    rows_ref[n:n + 1, :] = coef
    by_token = jnp.transpose(rows_ref[...])
    for i, cols in enumerate(lanes):
        dx = (dpass_ref[:, cols].astype(F32) + _column(by_token, i) * du
              + _column(by_token, n) * x_ref[:, cols].astype(F32)
              + jnp.dot(dm_rows, wb_ref[:, cols],
                        preferred_element_type=F32))
        dx_ref[:, cols] = dx.astype(dx_ref.dtype)


def _tile(rows: int, columns: int):
    from jax.experimental import pallas as pl

    return pl.BlockSpec((None, rows, columns), lambda b, i: (b, i, 0))


def _whole(shape):
    from jax.experimental import pallas as pl

    return pl.BlockSpec(shape, lambda b, i: (0,) * len(shape))


def _broadcast_rows(v, rows: int):
    """v [width] -> [width, rows] float32."""
    return jnp.broadcast_to(v.astype(F32)[:, None], (v.shape[0], rows))


@functools.partial(jax.jit, static_argnums=(4, 5))
def _pre_forward(x, w_hc, scale, base, hc: HC, out_dtype=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, nd = x.shape
    rows, width = _plan(x, hc), hc.width
    return pl.pallas_call(
        functools.partial(_pre_fwd_kernel, hc=hc),
        grid=(b, s // rows),
        in_specs=[_tile(rows, nd), _whole((MIX_COLUMNS, nd)),
                  _whole((width, rows)), _whole((width, rows))],
        out_specs=[_tile(rows, nd // hc.n), _tile(rows, MIX_COLUMNS)],
        out_shape=[jax.ShapeDtypeStruct((b, s, nd // hc.n),
                                        out_dtype or x.dtype),
                   jax.ShapeDtypeStruct((b, s, MIX_COLUMNS), F32)],
        scratch_shapes=[pltpu.VMEM((MIX_COLUMNS, rows), F32)],
        compiler_params=_compiler_params(("parallel", "parallel")),
        interpret=dispatch.interpret_mode(),
        name="hc_pre_fwd",
    )(x, _parts(w_hc, 3), _broadcast_rows(_scale_rows(scale, hc), rows),
      _broadcast_rows(base, rows))


@functools.partial(jax.jit, static_argnums=(7,))
def _pre_backward(x, w_hc, scale, base, du, dmix, dpass, hc: HC):
    """-> (dx like x: `dpass`, the cotangent of the stream handed on to
    `hc_post`, with this call's added, written over it; dw_hc, dscale [3],
    dbase, float32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, nd = x.shape
    rows, n, width = _plan(x, hc), hc.n, hc.width
    parts = _parts(w_hc, 2)
    # W against dm's parts [hi | lo | hi]: hi, hi, lo
    back = jnp.concatenate([parts[:PART_ROWS], parts[:PART_ROWS],
                            parts[PART_ROWS:2 * PART_ROWS],
                            jnp.zeros((MIX_COLUMNS - 3 * PART_ROWS, nd),
                                      parts.dtype)])
    dx, dw, dscale, dbase = pl.pallas_call(
        functools.partial(_pre_bwd_kernel, hc=hc),
        grid=(b, s // rows),
        in_specs=[_tile(rows, nd), _whole((MIX_COLUMNS, nd)),
                  _whole((MIX_COLUMNS, nd)), _whole((width, rows)),
                  _whole((width, rows)), _tile(rows, nd // n),
                  _tile(rows, MIX_COLUMNS), _tile(rows, nd)],
        out_specs=[_tile(rows, nd), _whole((MIX_COLUMNS, nd)),
                   _whole((width, rows)), _whole((width, rows))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((MIX_COLUMNS, nd), F32),
                   jax.ShapeDtypeStruct((width, rows), F32),
                   jax.ShapeDtypeStruct((width, rows), F32)],
        scratch_shapes=[pltpu.VMEM((MIX_COLUMNS, rows), F32)],
        input_output_aliases={7: 0},
        compiler_params=_compiler_params(("arbitrary", "arbitrary")),
        interpret=dispatch.interpret_mode(),
        name="hc_pre_bwd",
    )(x, _parts(w_hc, 3), back,
      _broadcast_rows(_scale_rows(scale, hc), rows),
      _broadcast_rows(base, rows), du, dmix, dpass)
    dw = (dw[:width] + dw[PART_ROWS:PART_ROWS + width]).T
    dscale = dscale.sum(axis=1)
    return dx, dw, jnp.stack([dscale[:n].sum(), dscale[n:2 * n].sum(),
                              dscale[2 * n:].sum()]), dbase.sum(axis=1)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _post_forward(x, y, mix, hc: HC, out_dtype=None):
    from jax.experimental import pallas as pl

    b, s, nd = x.shape
    rows = _plan(x, hc)
    return pl.pallas_call(
        functools.partial(_post_fwd_kernel, hc=hc),
        grid=(b, s // rows),
        in_specs=[_tile(rows, nd), _tile(rows, nd // hc.n),
                  _tile(rows, MIX_COLUMNS)],
        out_specs=_tile(rows, nd),
        out_shape=jax.ShapeDtypeStruct(x.shape, out_dtype or x.dtype),
        compiler_params=_compiler_params(("parallel", "parallel")),
        interpret=dispatch.interpret_mode(),
        name="hc_post_fwd",
    )(x, y, mix)


@functools.partial(jax.jit, static_argnums=(4,))
def _post_backward(x, y, mix, g, hc: HC):
    """-> (dx like x, written over g; dy like y; dmix)."""
    from jax.experimental import pallas as pl

    b, s, nd = x.shape
    rows = _plan(x, hc)
    return pl.pallas_call(
        functools.partial(_post_bwd_kernel, hc=hc),
        grid=(b, s // rows),
        in_specs=[_tile(rows, nd), _tile(rows, nd // hc.n),
                  _tile(rows, MIX_COLUMNS), _tile(rows, nd)],
        out_specs=[_tile(rows, nd), _tile(rows, nd // hc.n),
                   _tile(rows, MIX_COLUMNS)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(mix.shape, F32)],
        input_output_aliases={3: 0},
        compiler_params=_compiler_params(("parallel", "parallel")),
        interpret=dispatch.interpret_mode(),
        name="hc_post_bwd",
    )(x, y, mix, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _pallas_pre(x, w_hc, scale, base, hc, out_dtype):
    return (*_pre_forward(x, w_hc, scale, base, hc, out_dtype), x)


def _pre_vjp_fwd(x, w_hc, scale, base, hc, out_dtype):
    return (_pallas_pre(x, w_hc, scale, base, hc, out_dtype),
            (x, w_hc, scale, base))


def _pre_vjp_bwd(hc, out_dtype, res, cotangents):
    x, w_hc, scale, base = res
    du, dmix, dpass = cotangents
    dx, dw, dscale, dbase = _pre_backward(x, w_hc, scale, base, du,
                                          dmix.astype(F32), dpass, hc)
    return (dx, dw.astype(w_hc.dtype), dscale.astype(scale.dtype),
            dbase.astype(base.dtype))


_pallas_pre.defvjp(_pre_vjp_fwd, _pre_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _pallas_post(x, y, mix, hc, out_dtype):
    return _post_forward(x, y, mix, hc, out_dtype)


def _post_vjp_fwd(x, y, mix, hc, out_dtype):
    return _pallas_post(x, y, mix, hc, out_dtype), (x, y, mix)


def _post_vjp_bwd(hc, out_dtype, res, g):
    # the pass writes dx over dx', so takes it in the stream's dtype
    return tuple(_post_backward(*res, g.astype(res[0].dtype), hc))


_pallas_post.defvjp(_post_vjp_fwd, _post_vjp_bwd)


# ---------------------------------------------------------------------------
# The op
# ---------------------------------------------------------------------------

def path(x, hc: HC) -> str:
    """Which way a call goes: "pallas", "interpret" or "xla"."""
    interpret = dispatch.interpret_mode()
    mesh = jax.sharding.get_abstract_mesh()
    if (_plan(x, hc) is None
            or not (mesh is None or mesh.empty or mesh.size == 1)
            or not (interpret or dispatch.platform() == "tpu")):
        return "xla"
    return "interpret" if interpret else "pallas"


def hc_pre(x, w_hc, scale, base, hc: HC, out_dtype=None):
    """x [b, s, n d]; w_hc [n d, 2 n + n^2], scale [3], base [2 n + n^2] ->
    (u [b, s, d] in x's dtype or `out_dtype`, mix [b, s, 128] float32, x
    AGAIN), as the header says.  Hand the third to `hc_post` in x's place: it is x, and its
    cotangent then reaches this call's backward pass as an operand, which
    adds its own and writes over it, where the two calls' cotangents of one
    x would be added by a pass of XLA's over the stream."""
    if w_hc.shape != (x.shape[-1], hc.width) or scale.shape != (3,) \
            or base.shape != (hc.width,):
        raise ValueError(f"w_hc {w_hc.shape}, scale {scale.shape}, base "
                         f"{base.shape} for x {x.shape} and {hc}")
    taken = path(x, hc)
    dispatch.record("hyper_connection", taken)
    if taken == "xla":
        return (*_xla_pre(x, w_hc, scale, base, hc, out_dtype), x)
    return _pallas_pre(x, w_hc, scale, base, hc, out_dtype)


def hc_post(x, y, mix, hc: HC, out_dtype=None):
    """x [b, s, n d], y [b, s, d], mix as `hc_pre` gave it -> x' like x, in
    x's dtype or `out_dtype` (float32: the pass's own numbers, before the
    rounding the stream crosses HBM in)."""
    if y.shape != (*x.shape[:-1], x.shape[-1] // hc.n) \
            or mix.shape != (*x.shape[:-1], MIX_COLUMNS):
        raise ValueError(f"y {y.shape} and mix {mix.shape} for x {x.shape}")
    taken = path(x, hc)
    dispatch.record("hyper_connection", taken)
    if taken == "xla":
        return _xla_post(x, y, mix, hc, out_dtype)
    return _pallas_post(x, y, mix, hc, out_dtype)


def hc_collapse(x, w_head, scale_h, base_h, hc: HC):
    """The lanes' weighted sum behind the last layer: x [b, s, n d]; w_head
    [n d, n], scale_h [1], base_h [n] -> [b, s, d].  `hc_pre` with the
    columns of post and comb zero (their results are dropped, their
    cotangents zero)."""
    n = hc.n
    u, _, _ = hc_pre(
        x, jnp.pad(w_head, ((0, 0), (0, hc.width - n))),
        jnp.concatenate([scale_h, jnp.zeros((2,), scale_h.dtype)]),
        jnp.pad(base_h, (0, hc.width - n)), hc)
    return u


def hc_collapse_reference(x, w_head, scale_h, base_h, hc: HC):
    xf = x.astype(F32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + hc.norm_eps)
    m = jnp.einsum("...k,kw->...w", xf, w_head.astype(F32),
                   precision=jax.lax.Precision.HIGHEST) * r
    pre = jax.nn.sigmoid(scale_h[0] * m + base_h) + hc.eps
    return jnp.einsum("...i,...id->...d", pre,
                      xf.reshape(*x.shape[:-1], hc.n, -1)).astype(x.dtype)
