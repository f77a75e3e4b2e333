"""The state-space-dual recurrence (Mamba-2, arXiv:2405.21060): a matrix
state a head with a scalar decay, in its chunked form: Pallas TPU kernels,
forward and backward, with an XLA formulation elsewhere.

A head h of P channels, float32, S [P, N] from zero, reading group g = h //
(heads / groups):

    S_t = exp(dt_t a_h) S_(t-1) + dt_t x_t (x) B_t
    y_t = S_t C_t + D_h x_t

x: [batch, time, heads, P]; dt (> 0): [batch, time, heads] float32; a (< 0)
and D: [heads] float32; B, C: [batch, time, groups, N]; y like x.

The chunked form (`_chunk_forward`).  Inside a chunk of C steps, with L_t
the running sum of dt a from the chunk's first step, Lc its last and S the
state the chunk starts from:

    Y  = ((C_g B_g^T) o M o dt_s) X + exp(L) o (C_g S^T) + D X
    S' = exp(Lc) S + ((exp(Lc - L) dt) o X)^T B_g

M[t, s] = exp(L_t - L_s) for s <= t and 0 above.  Every decay is the exp of
a DIFFERENCE of running sums that is <= 0 where it is used, never a quotient
of exponentials.  C_g B_g^T is made once a group, the masked product once a
head.  There is no inverse (ops/gated_delta.py's rule pays one a chunk for
its delta term, which this rule does not have); what the two share in form
they share in code: the operands' parts on their way to the MXU (`_parts`,
`_mxu`: a float32 operand goes as two bfloat16 parts, so neither the state
nor a decay is rounded to bfloat16 on its way into a product), the running
sums a chunk a row and the row / column turns, the pass count, the padding.

The kernels.  Grid (batch x groups, blocks of time), the second axis
sequential; a block is `BLOCK_CHUNKS` chunks, and a program works ALL the
heads of one group, so B, C and C B^T are loaded and made once a group.  x
and y cross HBM as [b, t, heads x P], B and C as [b, t, groups x N]; the
state of a group's heads lies TRANSPOSED and side by side, S^T [N, heads x
P] float32, in a VMEM scratch across a (batch, group)'s blocks: the
products with the state are then one matmul a group ([C, N] x [N, heads x
P]), and where P is a fraction of a lane tile (64: two heads a tile) no
head is ever sliced out of its tile: the one product that is a head's own,
(C B^T o M_h) X_h, is made against the head's whole TILE and the head's
lanes selected from it (at an output of 128 columns the MXU takes as long
as at 64).  dt and the running sums come a chunk a row, [b x heads, chunks,
C].  Under differentiation the forward also writes the state every block
starts from ([b x groups, blocks, N, heads x P] float32); the backward
kernel walks the blocks in reverse: a block's chunks forward from that
state (the states alone), then back through them carrying dL/dS
(`_chunk_backward`), and sums dB and dC over the group's heads INSIDE the
kernel (they are products over all the group's lanes).  It gives dx, dB,
dC, ddt and dL (turned into the gradient of dt a by the running sum's own
transpose, outside).  D's term is in the forward kernel (x is there); its
gradient is a sum over dy x, made outside.  A sequence that is no multiple
of the block is padded with dt = 0, x = 0: a padded step leaves the state
alone.

The rule for taking the kernels: P divides a lane tile (128 % P == 0), a
group's heads fill whole lane tiles (heads / groups x P a multiple of 128),
N a multiple of 128; on a TPU, or interpreted where
RAY_TPU_PALLAS_INTERPRET=1 asks.  Elsewhere `ssd_scan_xla`, the same chunk
lines as einsums under a scan over checkpointed chunks.  Under an ambient
multi-device mesh the kernels run per shard inside a shard_map, batch over
the data/fsdp axes.  `dispatch.taken()` holds the path under "ssd_scan" and
the plan under "ssd_scan.plan".
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops import dispatch
from ray_tpu.ops.gated_delta import (_chunk_sums, _count_dots, _mxu, _pad_time,
                                     _parts, _square_indices, _to_column,
                                     _to_row)

F32 = jnp.float32
DEFAULT_CHUNK = 128
BLOCK_CHUNKS = 8        # chunks a grid step walks: 1024 steps at chunk 128
LANES = 128


# ---------------------------------------------------------------------------
# The recurrence as written: the ground truth of the tests
# ---------------------------------------------------------------------------

def ssd_scan_reference(x, dt, a, B, C, D):
    """The header's two lines, one step at a time, float32."""
    heads, groups = x.shape[2], B.shape[2]
    x, dt, a, D = (v.astype(F32) for v in (x, dt, a, D))
    B, C = (jnp.repeat(v.astype(F32), heads // groups, axis=2)
            for v in (B, C))

    def step(S, inp):       # S [b, h, P, N]
        x_t, dt_t, B_t, C_t = inp
        S = (jnp.exp(dt_t * a)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * B_t[..., None, :])
        return S, (jnp.einsum("bhpn,bhn->bhp", S, C_t)
                   + D[None, :, None] * x_t)

    _, y = jax.lax.scan(
        step, jnp.zeros(x.shape[:1] + x.shape[2:] + B.shape[-1:], F32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)


# ---------------------------------------------------------------------------
# XLA formulation: the chunk lines as einsums, chunks checkpointed
# ---------------------------------------------------------------------------

def ssd_scan_xla(x, dt, a, B, C, D, chunk: int = DEFAULT_CHUNK):
    """The chunked form in `jax.numpy`, float32: the fallback off the TPU.
    JAX differentiates it (one state a chunk is kept)."""
    b, t, heads, _ = x.shape
    groups = B.shape[2]
    pad = -t % chunk
    n = (t + pad) // chunk
    a = a.astype(F32)
    xs, dts, Bs, Cs = (
        jnp.moveaxis(_pad_time(v.astype(F32), pad).reshape(
            b, n, chunk, *v.shape[2:]), 1, 0) for v in (x, dt, B, C))
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    dot = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)

    @jax.checkpoint
    def one_chunk(S, inp):          # S [b, h, P, N]
        x, dt, B, C = inp           # [b, c, h, P], [b, c, h], [b, c, g, N]
        L = jnp.cumsum(dt * a, axis=1)
        CB = jnp.repeat(dot("btgn,bsgn->bgts", C, B), heads // groups, axis=1)
        B, C = (jnp.repeat(v, heads // groups, axis=2) for v in (B, C))
        Lh = jnp.moveaxis(L, 1, 2)                          # [b, h, c]
        M = jnp.where(lower, jnp.exp(jnp.where(
            lower, Lh[..., :, None] - Lh[..., None, :], 0.0)), 0.0)
        W = CB * M * jnp.moveaxis(dt, 1, 2)[..., None, :]
        y = (dot("bhts,bshp->bthp", W, x)
             + jnp.exp(L)[..., None] * dot("bthn,bhpn->bthp", C, S))
        last = L[:, -1:]                                    # [b, 1, h]
        S = (jnp.exp(last[:, 0])[..., None, None] * S
             + dot("bshp,bshn->bhpn",
                   (jnp.exp(last - L) * dt)[..., None] * x, B))
        return S, y

    _, y = jax.lax.scan(
        one_chunk, jnp.zeros((b, heads, x.shape[3], B.shape[3]), F32),
        (xs, dts, Bs, Cs))
    y = jnp.moveaxis(y, 0, 1).reshape(b, t + pad, *x.shape[2:])[:, :t]
    return (y + D.astype(F32)[:, None] * x.astype(F32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# One chunk of one GROUP: the kernels' body, on what they load into VMEM
# ---------------------------------------------------------------------------

def _slot(rows: int, P: int):
    """Which of a lane tile's heads a lane belongs to, [rows, 128]."""
    return jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1) // P


def _wide(cols, P: int):
    """cols: a group's heads' [rows, 1] each -> [rows, heads x P], head h's
    P lanes holding cols[h]: a lane tile's heads selected into it."""
    per, rows = LANES // P, cols[0].shape[0]
    slot = _slot(rows, P)
    tiles = []
    for first in range(0, len(cols), per):
        tile = jnp.broadcast_to(cols[first], (rows, LANES))
        for r in range(1, per):
            tile = jnp.where(slot == r, cols[first + r], tile)
        tiles.append(tile)
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)


def _head_sums(wide, P: int):
    """wide [rows, heads x P] -> a head's [rows, 1] each: its lanes' sum."""
    per, slot = LANES // P, _slot(wide.shape[0], P)
    out = []
    for tile in range(wide.shape[1] // LANES):
        t = wide[:, tile * LANES:(tile + 1) * LANES]
        for r in range(per):
            out.append(jnp.sum(t if per == 1 else jnp.where(slot == r, t, 0.0),
                               axis=1, keepdims=True))
    return out


def _head_scalars(dt_row, L_row):
    """A head's chunk scalars from its rows [1, C]: the columns [C, 1] L, dt,
    gamma = exp(L), tail = exp(Lc - L); Lc [1, 1]; and the masked decays
    M [C, C] = exp(L_t - L_s) on and under the diagonal."""
    C = L_row.shape[-1]
    i, j = _square_indices(C)
    lower = i >= j
    last = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1) == C - 1
    L, dt = _to_column(L_row), _to_column(dt_row)
    Lc = jnp.sum(jnp.where(last, L_row, 0.0), axis=-1, keepdims=True)
    # exp(L_t - L_s) where t >= s: the difference is <= 0 there
    M = jnp.where(lower, jnp.exp(jnp.where(lower, L - L_row, 0.0)), 0.0)
    return dict(L=L, dt=dt, Lc=Lc, last=last, gamma=jnp.exp(L),
                tail=jnp.exp(Lc - L), M=M)


def _own_product(x, P: int, per_head):
    """[C, heads x P] from `per_head(h, the head's lane tile of x in parts)
    -> [C, 128]`, the head's lanes of each result selected into its tile."""
    per, slot = LANES // P, _slot(x.shape[0], P)
    tiles = []
    for tile in range(x.shape[1] // LANES):
        xt = _parts(x[:, tile * LANES:(tile + 1) * LANES])
        out = None
        for r in range(per):
            o = per_head(tile * per + r, xt)
            out = o if out is None else jnp.where(slot == r, o, out)
        tiles.append(out)
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)


def _state_after(x, Bm, heads, St, P: int):
    """S'^T = exp(Lc) S^T + B^T ((tail dt) o X): St [N, heads x P]."""
    xw = _wide([h["tail"] * h["dt"] for h in heads], P) * x.astype(F32)
    return (_wide([jnp.exp(h["Lc"]) for h in heads], P) * St
            + _mxu(Bm, xw, 0, 0))


def _chunk_forward(x, Bm, Cm, dt_rows, L_rows, d_wide, St, P: int):
    """One chunk of one group: x [C, heads x P]; Bm, Cm [C, N]; dt_rows,
    L_rows [heads, 1, C] float32; d_wide [1, heads x P] (D a lane); St [N,
    heads x P] float32, the state the chunk starts from.  -> (y [C, heads x
    P] float32, the state after the chunk)."""
    heads = [_head_scalars(dt_rows[h], L_rows[h])
             for h in range(dt_rows.shape[0])]
    CB = _mxu(Cm, Bm, 1, 1)                             # once a group
    y = _own_product(x, P, lambda h, xt: _mxu(
        CB * heads[h]["M"] * dt_rows[h], xt))
    y = (y + _wide([h["gamma"] for h in heads], P) * _mxu(Cm, St)
         + d_wide * x.astype(F32))
    return y, _state_after(x, Bm, heads, St, P)


def _chunk_backward(x, Bm, Cm, dt_rows, L_rows, d_wide, St, dy, dSt, P: int):
    """The chunk walked back from dy [C, heads x P] and dL/dS'^T [N, heads
    x P]: -> (dx [C, heads x P], dB, dC [C, N] summed over the group's
    heads, ddt and dL rows [1, C] a head, dL/dS^T), float32.  dL is with
    respect to the running sums; the caller turns it into the gradient of
    dt a."""
    n_heads = dt_rows.shape[0]
    heads = [_head_scalars(dt_rows[h], L_rows[h]) for h in range(n_heads)]
    slot, per = _slot(x.shape[0], P), LANES // P
    xf, dyf = x.astype(F32), dy.astype(F32)
    St_parts, dSt_parts = _parts(St), _parts(dSt)
    CB = _mxu(Cm, Bm, 1, 1)
    K_dS = _mxu(Bm, dSt_parts)                          # [C, heads x P]
    r = _head_sums(xf * K_dS, P)            # d(tail dt), a head
    q = _head_sums(dyf * _mxu(Cm, St_parts), P)         # d gamma
    e = _head_sums(jnp.sum(dSt * St, axis=0, keepdims=True), P)
    ddt_rows, dL_rows, dCB_terms = [], [], []

    def own(h, xt):
        s = heads[h]
        dt_row, tile = dt_rows[h], h // per
        dy_t = dy[:, tile * LANES:(tile + 1) * LANES]
        mine = dy_t if per == 1 else jnp.where(
            slot == h % per, dy_t, jnp.zeros((), dy.dtype))
        dW = _mxu(mine, xt, 1, 1)                       # dy_h X_h^T
        Z = dW * CB * s["M"]
        Zd = Z * dt_row
        moved = s["tail"] * s["dt"] * r[h]
        dL = (jnp.sum(Zd, axis=1, keepdims=True) + s["gamma"] * q[h] - moved)
        to_last = (jnp.sum(moved, axis=0, keepdims=True)
                   + jnp.exp(s["Lc"]) * e[h])
        dL_rows.append(_to_row(dL) - jnp.sum(Zd, axis=0, keepdims=True)
                       + jnp.where(s["last"], to_last, 0.0))
        ddt_rows.append(jnp.sum(Z, axis=0, keepdims=True)
                        + _to_row(s["tail"] * r[h]))
        dCB_terms.append(dW * s["M"] * dt_row)
        return _mxu(CB * s["M"] * dt_row, dy_t, 0, 0)   # W^T dy

    tdt = _wide([h["tail"] * h["dt"] for h in heads], P)
    dx = _own_product(x, P, own) + tdt * K_dS + d_wide * dyf
    g_dy = _wide([h["gamma"] for h in heads], P) * dyf
    dCB_parts = _parts(sum(dCB_terms))     # the group's heads summed
    dC = _mxu(g_dy, St_parts, 1, 1) + _mxu(dCB_parts, Bm)
    dB = _mxu(tdt * xf, dSt_parts, 1, 1) + _mxu(dCB_parts, Cm, 0, 0)
    dSt = (_wide([jnp.exp(h["Lc"]) for h in heads], P) * dSt
           + _mxu(Cm, g_dy, 0, 0))
    return dx, dB, dC, ddt_rows, dL_rows, dSt


@functools.lru_cache(maxsize=None)
def mxu_passes(chunk: int, heads: int, P: int, N: int, dtype):
    """(forward, backward): the MXU passes of the two kernels' bodies a
    chunk a head, counted from the chunk lines as traced at these widths for
    the `heads` heads of a group; the backward's is its walk forward (the
    states alone) and its walk back.  The plan's `passes<fwd>+<bwd>`."""
    sds = jax.ShapeDtypeStruct
    wide = sds((chunk, heads * P), dtype)
    BC, rows = sds((chunk, N), dtype), sds((heads, 1, chunk), F32)
    d, St = sds((1, heads * P), F32), sds((N, heads * P), F32)

    def count(f, *args):
        return _count_dots(jax.make_jaxpr(f)(*args).jaxpr) / heads

    def states(x, Bm, dt_rows, L_rows, St):
        return _state_after(x, Bm, [_head_scalars(dt_rows[h], L_rows[h])
                                    for h in range(heads)], St, P)

    fwd = functools.partial(_chunk_forward, P=P)
    bwd = functools.partial(_chunk_backward, P=P)
    return (count(fwd, wide, BC, BC, rows, rows, d, St),
            count(states, wide, BC, rows, rows, St)
            + count(bwd, wide, BC, BC, rows, rows, d, St, wide, St))


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _chunk_rows(c, chunk: int):
    from jax.experimental import pallas as pl

    return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)


def _fwd_kernel(x_ref, B_ref, C_ref, dt_ref, L_ref, d_ref, y_ref, *rest,
                chunk: int, chunks: int, P: int, save_states: bool):
    from jax.experimental import pallas as pl

    if save_states:
        first_ref, St_ref = rest
    else:
        St_ref, = rest

    @pl.when(pl.program_id(1) == 0)
    def _():
        St_ref[...] = jnp.zeros_like(St_ref)

    if save_states:
        first_ref[...] = St_ref[...]    # the state this block starts from

    def one_chunk(c, carry):
        rows = _chunk_rows(c, chunk)
        y, St = _chunk_forward(
            x_ref[rows, :], B_ref[rows, :], C_ref[rows, :],
            dt_ref[:, pl.ds(c, 1), :], L_ref[:, pl.ds(c, 1), :], d_ref[...],
            St_ref[...], P)
        y_ref[rows, :] = y.astype(y_ref.dtype)
        St_ref[...] = St
        return carry

    jax.lax.fori_loop(0, chunks, one_chunk, 0)


def _bwd_kernel(x_ref, B_ref, C_ref, dt_ref, L_ref, d_ref, dy_ref, first_ref,
                dx_ref, dB_ref, dC_ref, ddt_ref, dL_ref, dSt_ref, states_ref,
                *, chunk: int, chunks: int, P: int):
    from jax.experimental import pallas as pl

    n_heads = dt_ref.shape[0]

    @pl.when(pl.program_id(1) == 0)         # the LAST block in time
    def _():
        dSt_ref[...] = jnp.zeros_like(dSt_ref)

    def operands(c):
        rows = _chunk_rows(c, chunk)
        return rows, (x_ref[rows, :], B_ref[rows, :], C_ref[rows, :],
                      dt_ref[:, pl.ds(c, 1), :], L_ref[:, pl.ds(c, 1), :])

    # 1. forward through the block's chunks: the state each starts from
    states_ref[0] = first_ref[...]

    def again(c, carry):
        _, (x, Bm, _, dt_rows, L_rows) = operands(c)
        states_ref[c + 1] = _state_after(
            x, Bm, [_head_scalars(dt_rows[h], L_rows[h])
                    for h in range(n_heads)], states_ref[c], P)
        return carry

    jax.lax.fori_loop(0, chunks - 1, again, 0)

    # 2. back through the chunks, dL/dS carried
    def back(i, carry):
        c = chunks - 1 - i
        rows, ops = operands(c)
        dx, dB, dC, ddt, dL, dSt = _chunk_backward(
            *ops, d_ref[...], states_ref[c], dy_ref[rows, :], dSt_ref[...], P)
        dx_ref[rows, :] = dx.astype(dx_ref.dtype)
        dB_ref[rows, :] = dB.astype(dB_ref.dtype)
        dC_ref[rows, :] = dC.astype(dC_ref.dtype)
        for h in range(n_heads):
            ddt_ref[h, pl.ds(c, 1), :] = ddt[h]
            dL_ref[h, pl.ds(c, 1), :] = dL[h]
        dSt_ref[...] = dSt
        return carry

    jax.lax.fori_loop(0, chunks, back, 0)


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=64 << 20)


def _specs(shapes, chunk: int, chunks: int, block_of):
    """The BlockSpecs both kernels share, for program (p, i) working the
    time block `block_of(i)` of batch p // groups' group p % groups: x (and
    y, dy, dx) at the group's heads, B and C (and dB, dC) at the group, the
    chunk rows of dt and L, D a lane, a block's first state."""
    from jax.experimental import pallas as pl

    heads, groups, P, N = shapes
    rows, wide = chunk * chunks, heads // groups * P
    at_heads = pl.BlockSpec((None, rows, wide), lambda p, i: (
        p // groups, block_of(i), p % groups))
    at_group = pl.BlockSpec((None, rows, N), lambda p, i: (
        p // groups, block_of(i), p % groups))
    scalars = pl.BlockSpec((heads // groups, chunks, chunk),
                           lambda p, i: (p, block_of(i), 0))
    d = pl.BlockSpec((1, wide), lambda p, i: (0, p % groups))
    state = pl.BlockSpec((None, None, N, wide),
                         lambda p, i: (p, block_of(i), 0, 0))
    return at_heads, at_group, scalars, d, state


def _blocks(t: int, chunk: int):
    chunks = min(BLOCK_CHUNKS, t // chunk)
    return chunks, t // (chunk * chunks)


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _scan_fwd(x3, B3, C3, dt, L, d_wide, shapes, chunk: int,
              save_states: bool):
    """x3 [b, T, heads x P]; B3, C3 [b, T, groups x N]; dt, L [b x heads, T
    / chunk, chunk] float32; d_wide [1, heads x P].  -> (y like x3, every
    block's first state or None)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    heads, groups, P, N = shapes
    b, t = x3.shape[:2]
    chunks, blocks = _blocks(t, chunk)
    wide = heads // groups * P
    at_heads, at_group, scalars, d, state = _specs(shapes, chunk, chunks,
                                                   lambda i: i)
    out_specs, out_shape = [at_heads], [jax.ShapeDtypeStruct(x3.shape,
                                                             x3.dtype)]
    if save_states:
        out_specs.append(state)
        out_shape.append(jax.ShapeDtypeStruct((b * groups, blocks, N, wide),
                                              F32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, chunks=chunks, P=P,
                          save_states=save_states),
        grid=(b * groups, blocks),
        in_specs=[at_heads, at_group, at_group, scalars, scalars, d],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((N, wide), F32)],
        compiler_params=_compiler_params(),
        interpret=dispatch.interpret_mode(),
        name="ssd_scan_fwd",
    )(x3, B3, C3, dt, L, d_wide)
    return tuple(out) if save_states else (out[0], None)


@functools.partial(jax.jit, static_argnums=(8, 9))
def _scan_bwd(x3, B3, C3, dt, L, d_wide, dy3, first, shapes, chunk: int):
    """-> (dx like x3, dB, dC like B3, ddt and dL like dt)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    heads, groups, P, N = shapes
    b, t = x3.shape[:2]
    chunks, blocks = _blocks(t, chunk)
    wide = heads // groups * P
    at_heads, at_group, scalars, d, state = _specs(
        shapes, chunk, chunks, lambda i: blocks - 1 - i)
    like = jax.ShapeDtypeStruct
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, chunks=chunks, P=P),
        grid=(b * groups, blocks),
        in_specs=[at_heads, at_group, at_group, scalars, scalars, d,
                  at_heads, state],
        out_specs=[at_heads, at_group, at_group, scalars, scalars],
        out_shape=[like(x3.shape, x3.dtype), like(B3.shape, B3.dtype),
                   like(C3.shape, C3.dtype), like(dt.shape, F32),
                   like(L.shape, F32)],
        scratch_shapes=[pltpu.VMEM((N, wide), F32),
                        pltpu.VMEM((chunks, N, wide), F32)],
        compiler_params=_compiler_params(),
        interpret=dispatch.interpret_mode(),
        name="ssd_scan_bwd",
    )(x3, B3, C3, dt, L, d_wide, dy3, first)


# ---------------------------------------------------------------------------
# custom VJP over the padded operands as the kernels take them
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x3, B3, C3, dt, L, d_wide, shapes, chunk):
    return _scan_fwd(x3, B3, C3, dt, L, d_wide, shapes, chunk, False)[0]


# What a caller's `jax.checkpoint` may keep of the scan (`save_only_these_
# names`): with y and the blocks' first states kept, its backward runs no
# forward kernel again.
KEPT_NAMES = ("ssd_out", "ssd_states")


def _scan_vjp_fwd(x3, B3, C3, dt, L, d_wide, shapes, chunk):
    y, first = _scan_fwd(x3, B3, C3, dt, L, d_wide, shapes, chunk, True)
    y, first = (checkpoint_name(v, n) for v, n in zip((y, first), KEPT_NAMES))
    return y, (x3, B3, C3, dt, L, d_wide, first)


def _scan_vjp_bwd(shapes, chunk, res, dy3):
    x3, B3, C3, dt, L, d_wide, first = res
    dx, dB, dC, ddt, dL = _scan_bwd(x3, B3, C3, dt, L, d_wide, dy3, first,
                                    shapes, chunk)
    dd = jnp.einsum("btw,btw->w", dy3, x3,
                    preferred_element_type=F32)[None, :]
    return dx, dB, dC, ddt, dL, dd


_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def _scan_pallas(x, dt, a, B, C, D, chunk: int):
    """Pad time to whole blocks, hand the operands over as the kernels take
    them and undo both on the way out (JAX differentiates the padding, the
    views, the running sum and D's broadcast)."""
    b, t, heads, P = x.shape
    groups, N = B.shape[2:]
    block = chunk * BLOCK_CHUNKS
    pad = -t % (chunk if t <= block else block)
    x, dt, B, C = (_pad_time(v, pad) for v in (x, dt, B, C))
    T = t + pad
    dt = dt.astype(F32)
    L = _chunk_sums(dt * a.astype(F32), chunk).reshape(
        b * heads, T // chunk, chunk)
    d_wide = jnp.repeat(D.astype(F32), P)[None, :]
    y = _scan(x.reshape(b, T, heads * P), B.reshape(b, T, groups * N),
              C.reshape(b, T, groups * N),
              dt.transpose(0, 2, 1).reshape(L.shape), L, d_wide,
              (heads, groups, P, N), chunk)
    return y.reshape(b, T, heads, P)[:, :t]


# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------

def takes_kernels(heads: int, groups: int, P: int, N: int) -> bool:
    """The header's rule, the shapes' part of it."""
    return (LANES % P == 0 and (heads // groups * P) % LANES == 0
            and N % LANES == 0)


def ssd_scan(x, dt, a, B, C, D, chunk: Optional[int] = None):
    """y of the recurrence in the module's header.  x: [b, T, heads, P]; dt:
    [b, T, heads] float32 (> 0); a, D: [heads] (a < 0); B, C: [b, T,
    groups, N]; -> y like x, in x's dtype, the state float32 from zero.

    On TPU (or interpreted, for tests) the Pallas kernels where the shapes
    allow (`takes_kernels`); elsewhere `ssd_scan_xla`."""
    chunk = chunk or DEFAULT_CHUNK
    heads, P = x.shape[2:]
    groups, N = B.shape[2:]
    if heads % groups:
        raise ValueError(f"{heads} heads over {groups} groups")
    interpret = dispatch.interpret_mode()
    if not takes_kernels(heads, groups, P, N) or (
            not interpret and dispatch.platform() != "tpu"):
        dispatch.record("ssd_scan", "xla")
        return ssd_scan_xla(x, dt, a, B, C, D, chunk)
    dispatch.record("ssd_scan", "interpret" if interpret else "pallas")
    forward, backward = mxu_passes(chunk, heads // groups, P, N,
                                   jnp.dtype(x.dtype))
    dispatch.record("ssd_scan.plan",
                    f"chunk{chunk},heads{heads}over{groups},p{P},n{N},"
                    f"state_f32,bwd_pallas,passes{forward:g}+{backward:g}")

    def kernel(x, dt, a, B, C, D):
        return _scan_pallas(x, dt, a, B, C, D, chunk)

    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or mesh.size == 1:
        return kernel(x, dt, a, B, C, D)
    from jax.sharding import PartitionSpec as Spec

    sizes = dict(mesh.shape)
    batch = tuple(n for n in ("data", "fsdp") if n in sizes)
    if x.shape[0] % math.prod(sizes[n] for n in batch):
        batch = ()
    row, whole = Spec(batch or None), Spec()
    return jax.shard_map(kernel, mesh=mesh,
                         in_specs=(row, row, whole, row, row, whole),
                         out_specs=row, check_vma=False)(x, dt, a, B, C, D)
