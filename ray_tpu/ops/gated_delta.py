"""The gated delta rule (Gated DeltaNet's linear attention): a matrix state a
head with a rank-one delta update, in its chunked form: Pallas TPU kernels,
forward and backward, with an XLA formulation elsewhere.

A value head, float32, S [d_k, d_v] from zero:

    S   <- exp(g_t) S
    d_t  = beta_t (v_t - S^T k_t)
    S   <- S + k_t d_t^T
    o_t  = S^T q_t

q, k: [batch, time, key heads, d_k]; v: [batch, time, value heads, d_v]; g
(<= 0, the log of the decay) and beta: [batch, time, value heads]; o like v.
Value head j reads key head j // (value heads / key heads).  Whoever calls
normalises q and k (the rule is written for |k| = 1).

The chunked form (`_chunk_forward`, the WY / UT transform).  Inside a chunk
of C steps, with G_i the running sum of g from the chunk's first step and S
the state the chunk starts from, the d_i solve a triangular system:

    A_ij = beta_i (k_i . k_j) exp(G_i - G_j)   (j < i, else 0)
    (I + A) D = beta (V - exp(G) (K S))
    O  = exp(G) (Q S) + tril(Q K^T exp(G_i - G_j)) D
    S' = exp(G_C) S + K^T (exp(G_C - G) D)

A is strictly lower triangular, so nilpotent: (I + A)^-1 = (I - A)(I + A^2)
(I + A^4) .. is EXACT after log2(C) factors (`_unit_lower_inverse`); nothing
is approximated.  Every decay is the exp of a DIFFERENCE of running sums
that is <= 0 where it is used, never a quotient of exponentials.

Precision.  The state, A, its inverse and D are float32.  A matmul's
operand that is bfloat16 goes to the MXU as it is; a float32 operand goes
as two bfloat16 parts, its rounding and the rest (`_dot`: hi x hi + hi x lo
+ lo x hi, an error near 2^-16 of the product), so the state is never
rounded to bfloat16 on its way into a product.  Row scalings are kept
OUTSIDE the products with K and Q (exp(G) (K S), not (exp(G) K) S), so that
bfloat16 keys and queries stay exact operands.

The kernels.  Grid (batch x value heads, blocks of time), the second axis
sequential; a block is `BLOCK_CHUNKS` chunks, walked in a loop (unrolled:
13.7 against 14.6 ms a forward call at 2 x 8192 x 32 heads, 42.0 against 47.2
forward and backward; PERF.md, PR 42) that carries the float32 state, which
crosses a head's blocks in a VMEM scratch.  q and k
are read where their projection laid them, [b, t, key heads x d_k], through
an index map at value head // group (GQA's repeat never exists in HBM), v
and o as [b, t, value heads x d_v].  The running sums G (made outside: a
cumulative sum over a chunk) and beta come a chunk a row, [b x heads,
chunks, C].  Under differentiation the forward also writes the state every
block starts from ([b x heads, blocks, d_k, d_v] float32); the backward
kernel walks the blocks in reverse, recomputes a block's chunk states from
that into VMEM, then steps back through its chunks carrying dL/dS
(`_chunk_backward`: every quantity of the chunk made again from its first
state).  It gives dq and dk a VALUE head (summed over a key head's group
outside), dv, dG (turned into dg by a reverse cumulative sum over the
chunk, outside) and dbeta.  A sequence that is no multiple of the block is
padded with k = 0, beta = 0, g = 0: a padded step leaves the state alone.

Off TPU: the interpreter when RAY_TPU_PALLAS_INTERPRET=1, else
`gated_delta_xla`, the same chunk function under `vmap` and a scan over
checkpointed chunks.  `dispatch.taken()` holds the path under
"gated_delta_rule" and the plan under "gated_delta_rule.plan".
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops import dispatch

F32, BF16 = jnp.float32, jnp.bfloat16
DEFAULT_CHUNK = 64
BLOCK_CHUNKS = 8        # chunks a grid step walks: 512 steps at chunk 64


# ---------------------------------------------------------------------------
# The recurrence as written: the ground truth of the tests
# ---------------------------------------------------------------------------

def gated_delta_reference(q, k, v, g, beta):
    """The header's four lines, one step at a time, float32."""
    hv, hk = v.shape[2], k.shape[2]
    q, k = (jnp.repeat(a.astype(F32), hv // hk, axis=2) for a in (q, k))
    v, g, beta = (a.astype(F32) for a in (v, g, beta))
    b, _, _, dk = k.shape

    def step(S, inp):       # S [b, h, dk, dv]
        q_t, k_t, v_t, g_t, beta_t = inp
        S = jnp.exp(g_t)[..., None, None] * S
        d = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., :, None] * d[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    _, o = jax.lax.scan(
        step, jnp.zeros((b, hv, dk, v.shape[-1]), F32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


# ---------------------------------------------------------------------------
# One chunk: 2-D arrays only, so that the same lines are the XLA path (under
# vmap) and the kernels' body (on what they load into VMEM)
# ---------------------------------------------------------------------------

def _parts(x):
    """x as the MXU takes it: itself if bfloat16, else its bfloat16
    rounding and what the rounding left."""
    if x.dtype == BF16:
        return (x,)
    hi = x.astype(BF16)
    return hi, (x - hi.astype(F32)).astype(BF16)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _dot(a, b, contract_a: int = 1, contract_b: int = 0):
    """a . b of two matrices over the named axes, float32: the products of
    the operands' parts but lo x lo.  Its derivative is the product's (the
    XLA path is differentiated through it; the split has none of its
    own)."""
    dims = (((contract_a,), (contract_b,)), ((), ()))
    total = None
    for i, pa in enumerate(_parts(a)):
        for j, pb in enumerate(_parts(b)):
            if i + j < 2:
                term = jax.lax.dot_general(pa, pb, dims,
                                           preferred_element_type=F32)
                total = term if total is None else total + term
    return total


def _dot_fwd(a, b, contract_a, contract_b):
    return _dot(a, b, contract_a, contract_b), (a, b)


def _dot_bwd(contract_a, contract_b, res, ct):
    a, b = res
    da = _dot(ct, b, 1, 1 - contract_b) if contract_a == 1 \
        else _dot(b, ct, 1 - contract_b, 1)
    db = _dot(a, ct, 1 - contract_a, 0) if contract_b == 0 \
        else _dot(ct, a, 0, 1 - contract_a)
    return da.astype(a.dtype), db.astype(b.dtype)


_dot.defvjp(_dot_fwd, _dot_bwd)


def _square_indices(n: int):
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    return row, jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)


def _to_column(row):
    """[1, n] -> [n, 1] without a transpose: the diagonal of its broadcast,
    summed along lanes."""
    n = row.shape[1]
    i, j = _square_indices(n)
    return jnp.sum(jnp.where(i == j, jnp.broadcast_to(row, (n, n)), 0.0),
                   axis=1, keepdims=True)


def _to_row(column):
    """[n, 1] -> [1, n], likewise, summed along sublanes."""
    n = column.shape[0]
    i, j = _square_indices(n)
    return jnp.sum(jnp.where(i == j, jnp.broadcast_to(column, (n, n)), 0.0),
                   axis=0, keepdims=True)


def _unit_lower_inverse(a):
    """(I + a)^-1 for a strictly lower triangular [n, n]: with b = -a,
    (I + b)(I + b^2)(I + b^4) .., exact once the power reaches n."""
    n = a.shape[0]
    i, j = _square_indices(n)
    power = -a
    inverse = jnp.where(i == j, 1.0, 0.0) + power
    for _ in range(max(0, math.ceil(math.log2(n)) - 1)):
        power = _dot(power, power)
        inverse = inverse + _dot(inverse, power)
    return inverse


def _chunk_quantities(q, k, v, G_row, beta_row, S):
    """What forward and backward both need of a chunk.  q, k [C, d_k], v
    [C, d_v], G_row and beta_row [1, C] float32 (G the running sum of g
    inside the chunk), S [d_k, d_v] float32, the state the chunk starts
    from."""
    C = q.shape[0]
    i, j = _square_indices(C)
    strict, lower = i > j, i >= j
    G = _to_column(G_row)
    beta = _to_column(beta_row)
    # exp(G_i - G_j) where i >= j: the difference is <= 0 there
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, G - G_row, 0.0)), 0.0)
    M = jnp.where(strict, _dot(k, k, 1, 1) * decay, 0.0)
    A = beta * M
    T = _unit_lower_inverse(A)
    gamma = jnp.exp(G)                                  # [C, 1]
    KS = gamma * _dot(k, S)
    rest = v.astype(F32) - KS                           # v - exp(G) K S
    D = _dot(T, beta * rest)
    P = jnp.where(lower, _dot(q, k, 1, 1) * decay, 0.0)
    QS = gamma * _dot(q, S)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1) == C - 1
    G_last = jnp.sum(jnp.where(last, G_row, 0.0), axis=1, keepdims=True)
    tail = jnp.exp(G_last - G)                          # exp(G_C - G_i)
    return dict(strict=strict, lower=lower, last=last, G=G, beta=beta,
                decay=decay, M=M,
                A=A, T=T, gamma=gamma, KS=KS, rest=rest, D=D, P=P, QS=QS,
                G_last=G_last, tail=tail)


def _decayed(S, G_last):
    """exp(G_C) S, G_C [1, 1]: broadcast along lanes, then sublanes."""
    return jnp.exp(jnp.broadcast_to(G_last, (1, S.shape[1]))) * S


def _chunk_forward(q, k, v, G_row, beta_row, S):
    """-> (o [C, d_v] float32, the state after the chunk)."""
    c = _chunk_quantities(q, k, v, G_row, beta_row, S)
    o = c["QS"] + _dot(c["P"], c["D"])
    S_next = _decayed(S, c["G_last"]) + _dot(k, c["tail"] * c["D"], 0, 0)
    return o, S_next


def _chunk_backward(q, k, v, G_row, beta_row, S, do, dS_next):
    """The chunk walked back from do [C, d_v] and dL/dS' [d_k, d_v]: ->
    (dq, dk [C, d_k], dv [C, d_v], dG and dbeta [1, C], dL/dS), float32.
    dG is with respect to the running sums; the caller sums it back over
    the chunk's later steps for dg."""
    c = _chunk_quantities(q, k, v, G_row, beta_row, S)
    C = q.shape[0]
    strict, lower, decay = c["strict"], c["lower"], c["decay"]
    beta, gamma, tail, D = c["beta"], c["gamma"], c["tail"], c["D"]
    do = do.astype(F32)

    # o = QS + P D;  S' = exp(G_C) S + K^T (tail D)
    K_dS = _dot(k, dS_next)                             # [C, d_v]
    dD = _dot(c["P"], do, 0, 0) + tail * K_dS
    dP = jnp.where(lower, _dot(do, D, 1, 1), 0.0)
    g_do = gamma * do
    dq = _dot(g_do, S, 1, 1)
    dS = _dot(q, g_do, 0, 0) + _decayed(dS_next, c["G_last"])
    dG = jnp.sum(do * c["QS"], axis=1, keepdims=True)   # [C, 1]
    # D = T (beta rest), T = (I + A)^-1
    dR = _dot(c["T"], dD, 0, 0)
    dA = -jnp.where(strict, _dot(dR, D, 1, 1), 0.0)
    dv = beta * dR
    dbeta = (jnp.sum(dR * c["rest"], axis=1, keepdims=True)
             + jnp.sum(dA * c["M"], axis=1, keepdims=True))
    # rest = v - KS, KS = gamma (K S)
    g_dKS = gamma * (0.0 - dv)
    dk = _dot(g_dKS, S, 1, 1)
    dS = dS + _dot(k, g_dKS, 0, 0)
    dG = dG - jnp.sum(dv * c["KS"], axis=1, keepdims=True)
    # A = beta M, M = (K K^T) decay;  P = (Q K^T) decay
    X = beta * dA * decay
    Y = dP * decay
    dk = dk + _dot(X, k) + _dot(X, k, 0, 0) + _dot(Y, q, 0, 0)
    dq = dq + _dot(Y, k)
    Z = dA * c["A"] + dP * c["P"]           # d(decay) x decay, elementwise
    dG = dG + jnp.sum(Z, axis=1, keepdims=True)
    dG_row = 0.0 - jnp.sum(Z, axis=0, keepdims=True)
    # S' again: tail = exp(G_C - G) on D's rows, exp(G_C) on S
    tD = tail * D
    dk = dk + _dot(tD, dS_next, 1, 1)
    d_tail = jnp.sum(tD * K_dS, axis=1, keepdims=True)  # x tail already
    dG = dG - d_tail
    to_last = (jnp.sum(d_tail, axis=0, keepdims=True)
               + jnp.exp(c["G_last"]) * jnp.sum(
                   jnp.sum(dS_next * S, axis=1, keepdims=True),
                   axis=0, keepdims=True))              # [1, 1]
    dG_row = dG_row + _to_row(dG) + jnp.where(c["last"], to_last, 0.0)
    return dq, dk, dv, dG_row, _to_row(dbeta), dS


# ---------------------------------------------------------------------------
# XLA formulation: the chunk function under vmap, chunks checkpointed
# ---------------------------------------------------------------------------

def _chunk_sums(g, chunk: int):
    """g [b, T, h] -> the running sums inside every chunk, [b, h, T / chunk,
    chunk] float32."""
    b, t, h = g.shape
    return jnp.cumsum(
        g.astype(F32).transpose(0, 2, 1).reshape(b, h, t // chunk, chunk),
        axis=-1)


def _pad_time(x, pad: int):
    return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))


def gated_delta_xla(q, k, v, g, beta, chunk: int = DEFAULT_CHUNK):
    """The chunked form in `jax.numpy`: the fallback off the TPU.  JAX
    differentiates it (one state a chunk is kept)."""
    b, t, hv, dv = v.shape
    hk, dk = k.shape[2:]
    pad = -t % chunk
    q, k, v, g, beta = (_pad_time(a, pad) for a in (q, k, v, g, beta))
    n = (t + pad) // chunk
    group = hv // hk

    def heads_first(a, h):          # [b, T, h, w] -> [n, b, h, chunk, w]
        return a.reshape(b, n, chunk, h, -1).transpose(1, 0, 3, 2, 4)

    qs, ks = (jnp.repeat(heads_first(a, hk), group, axis=2) for a in (q, k))
    vs = heads_first(v, hv)
    Gs = _chunk_sums(g, chunk).transpose(2, 0, 1, 3)[..., None, :]
    betas = beta.astype(F32).transpose(0, 2, 1).reshape(
        b, hv, n, 1, chunk).transpose(2, 0, 1, 3, 4)
    per_head = jax.vmap(jax.vmap(_chunk_forward))

    @jax.checkpoint
    def one_chunk(S, inp):
        o, S = per_head(*inp, S)
        return S, o

    _, o = jax.lax.scan(one_chunk, jnp.zeros((b, hv, dk, dv), F32),
                        (qs, ks, vs, Gs, betas))
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, t + pad, hv, dv)
    return o[:, :t].astype(v.dtype)


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _chunk_rows(c, chunk: int):
    from jax.experimental import pallas as pl

    return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)


def _fwd_kernel(q_ref, k_ref, v_ref, G_ref, beta_ref, o_ref, *rest,
                chunk: int, chunks: int, save_states: bool):
    from jax.experimental import pallas as pl

    if save_states:
        first_ref, S_ref = rest
    else:
        (S_ref,) = rest

    @pl.when(pl.program_id(1) == 0)
    def _():
        S_ref[...] = jnp.zeros_like(S_ref)

    if save_states:
        first_ref[...] = S_ref[...]     # the state this block starts from

    def one_chunk(c, S):
        rows = _chunk_rows(c, chunk)
        o, S = _chunk_forward(q_ref[rows, :], k_ref[rows, :], v_ref[rows, :],
                              G_ref[pl.ds(c, 1), :], beta_ref[pl.ds(c, 1), :],
                              S)
        o_ref[rows, :] = o.astype(o_ref.dtype)
        return S

    S_ref[...] = jax.lax.fori_loop(0, chunks, one_chunk, S_ref[...],
                                   unroll=True)


def _bwd_kernel(q_ref, k_ref, v_ref, G_ref, beta_ref, do_ref, first_ref,
                dq_ref, dk_ref, dv_ref, dG_ref, dbeta_ref,
                dS_ref, states_ref, *, chunk: int, chunks: int):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)         # the LAST block in time
    def _():
        dS_ref[...] = jnp.zeros_like(dS_ref)

    def operands(c):
        rows = _chunk_rows(c, chunk)
        return rows, (q_ref[rows, :], k_ref[rows, :], v_ref[rows, :],
                      G_ref[pl.ds(c, 1), :], beta_ref[pl.ds(c, 1), :])

    # 1. the block's chunk states again, each chunk's first state kept
    def again(c, S):
        states_ref[c] = S
        return _chunk_forward(*operands(c)[1], S)[1]

    jax.lax.fori_loop(0, chunks, again, first_ref[...], unroll=True)

    # 2. back through the chunks, dL/dS carried
    def back(i, dS):
        c = chunks - 1 - i
        rows, ops = operands(c)
        dq, dk, dv, dG, dbeta, dS = _chunk_backward(
            *ops, states_ref[c], do_ref[rows, :], dS)
        dq_ref[rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[rows, :] = dk.astype(dk_ref.dtype)
        dv_ref[rows, :] = dv.astype(dv_ref.dtype)
        dG_ref[pl.ds(c, 1), :] = dG
        dbeta_ref[pl.ds(c, 1), :] = dbeta
        return dS

    dS_ref[...] = jax.lax.fori_loop(0, chunks, back, dS_ref[...],
                                    unroll=True)


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=48 << 20)


def _specs(shapes, chunk: int, chunks: int, block_of):
    """The BlockSpecs both kernels share, for program (bh, i) working the
    time block `block_of(i)`: q and k at the head's KEY head, v (and o, do,
    dv) at the head, dq and dk a value head, the chunk rows of G and beta,
    a block's first state."""
    from jax.experimental import pallas as pl

    hv, hk, dk, dv = shapes
    rows, group = chunk * chunks, hv // hk
    key = pl.BlockSpec((None, rows, dk),
                       lambda bh, i: (bh // hv, block_of(i),
                                      (bh % hv) // group))
    per_value_head = pl.BlockSpec(
        (None, rows, dk), lambda bh, i: (bh // hv, block_of(i), bh % hv))
    value = pl.BlockSpec((None, rows, dv),
                         lambda bh, i: (bh // hv, block_of(i), bh % hv))
    scalars = pl.BlockSpec((None, chunks, chunk),
                           lambda bh, i: (bh, block_of(i), 0))
    state = pl.BlockSpec((None, None, dk, dv),
                         lambda bh, i: (bh, block_of(i), 0, 0))
    return key, per_value_head, value, scalars, state


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _rule_fwd(q3, k3, v3, G, beta, shapes, chunk: int, save_states: bool):
    """q3, k3 [b, T, hk x dk], v3 [b, T, hv x dv]; G, beta [b x hv, T /
    chunk, chunk] float32.  -> (o like v3, every block's first state or
    None)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hv, hk, dk, dv = shapes
    b, t = v3.shape[:2]
    chunks = min(BLOCK_CHUNKS, t // chunk)
    blocks = t // (chunk * chunks)
    key, _, value, scalars, state = _specs(shapes, chunk, chunks, lambda i: i)
    out_specs, out_shape = [value], [jax.ShapeDtypeStruct(v3.shape, v3.dtype)]
    if save_states:
        out_specs.append(state)
        out_shape.append(jax.ShapeDtypeStruct((b * hv, blocks, dk, dv), F32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, chunks=chunks,
                          save_states=save_states),
        grid=(b * hv, blocks),
        in_specs=[key, key, value, scalars, scalars],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((dk, dv), F32)],
        compiler_params=_compiler_params(),
        interpret=dispatch.interpret_mode(),
        name="gated_delta_fwd",
    )(q3, k3, v3, G, beta)
    return tuple(out) if save_states else (out[0], None)


@functools.partial(jax.jit, static_argnums=(7, 8))
def _rule_bwd(q3, k3, v3, G, beta, do3, first, shapes, chunk: int):
    """-> (dq, dk [b, T, hv x dk] a VALUE head, dv like v3, dG and dbeta
    like G)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hv, hk, dk, dv = shapes
    b, t = v3.shape[:2]
    chunks = min(BLOCK_CHUNKS, t // chunk)
    blocks = t // (chunk * chunks)
    key, per_value_head, value, scalars, state = _specs(
        shapes, chunk, chunks, lambda i: blocks - 1 - i)
    wide = jax.ShapeDtypeStruct((b, t, hv * dk), q3.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, chunks=chunks),
        grid=(b * hv, blocks),
        in_specs=[key, key, value, scalars, scalars, value, state],
        out_specs=[per_value_head, per_value_head, value, scalars, scalars],
        out_shape=[wide, wide, jax.ShapeDtypeStruct(v3.shape, v3.dtype),
                   jax.ShapeDtypeStruct(G.shape, F32),
                   jax.ShapeDtypeStruct(G.shape, F32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), F32),
                        pltpu.VMEM((chunks, dk, dv), F32)],
        compiler_params=_compiler_params(),
        interpret=dispatch.interpret_mode(),
        name="gated_delta_bwd",
    )(q3, k3, v3, G, beta, do3, first)


# ---------------------------------------------------------------------------
# custom VJP over the padded operands as the kernels take them
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q3, k3, v3, G, beta, shapes, chunk):
    return _rule_fwd(q3, k3, v3, G, beta, shapes, chunk, False)[0]


def _rule_vjp_fwd(q3, k3, v3, G, beta, shapes, chunk):
    o, first = _rule_fwd(q3, k3, v3, G, beta, shapes, chunk, True)
    return o, (q3, k3, v3, G, beta, first)


def _rule_vjp_bwd(shapes, chunk, res, do3):
    q3, k3, v3, G, beta, first = res
    hv, hk, dk, _ = shapes
    dq, dk_, dv, dG, dbeta = _rule_bwd(q3, k3, v3, G, beta, do3, first,
                                       shapes, chunk)

    def over_group(d):      # a key head's value heads summed, in float32
        b, t, _ = d.shape
        return jnp.sum(d.reshape(b, t, hk, hv // hk, dk), axis=3,
                       dtype=F32).reshape(b, t, hk * dk).astype(d.dtype)

    return over_group(dq), over_group(dk_), dv, dG, dbeta


_rule.defvjp(_rule_vjp_fwd, _rule_vjp_bwd)


def _rule_pallas(q, k, v, g, beta, chunk: int):
    """Pad time to whole blocks, hand the operands over as the kernels take
    them and undo both on the way out (JAX differentiates the padding, the
    views and the cumulative sum)."""
    b, t, hv, dv = v.shape
    hk, dk = k.shape[2:]
    block = chunk * BLOCK_CHUNKS
    pad = -t % (chunk if t <= block else block)
    q, k, v, g, beta = (_pad_time(a, pad) for a in (q, k, v, g, beta))
    T = t + pad
    G = _chunk_sums(g, chunk).reshape(b * hv, T // chunk, chunk)
    beta = beta.astype(F32).transpose(0, 2, 1).reshape(G.shape)
    o = _rule(q.reshape(b, T, hk * dk), k.reshape(b, T, hk * dk),
              v.reshape(b, T, hv * dv), G, beta, (hv, hk, dk, dv), chunk)
    return o.reshape(b, T, hv, dv)[:, :t]


# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------

def gated_delta_rule(q, k, v, g, beta, chunk: Optional[int] = None):
    """o of the recurrence in the module's header.  q, k: [b, T, key heads,
    d_k] (normalised by the caller); v: [b, T, value heads, d_v]; g, beta:
    [b, T, value heads]; -> o like v, in v's dtype.  g is the LOG of the
    decay (<= 0), beta in [0, 1].

    On TPU (or interpreted, for tests) the Pallas kernels; elsewhere
    `gated_delta_xla`.  Under an ambient multi-device mesh the kernels run
    per shard inside a shard_map, batch over the data/fsdp axes: GSPMD
    cannot partition a Mosaic kernel itself.
    """
    chunk = chunk or DEFAULT_CHUNK
    hv, hk = v.shape[2], k.shape[2]
    if hv % hk:
        raise ValueError(f"{hv} value heads over {hk} key heads")
    interpret = dispatch.interpret_mode()
    if not interpret and dispatch.platform() != "tpu":
        dispatch.record("gated_delta_rule", "xla")
        return gated_delta_xla(q, k, v, g, beta, chunk)
    dispatch.record("gated_delta_rule", "interpret" if interpret else "pallas")
    dispatch.record("gated_delta_rule.plan",
                    f"chunk{chunk},heads{hv}over{hk},dk{k.shape[-1]},"
                    f"dv{v.shape[-1]},state_f32,bwd_pallas")

    def kernel(q, k, v, g, beta):
        return _rule_pallas(q, k, v, g, beta, chunk)

    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or mesh.size == 1:
        return kernel(q, k, v, g, beta)
    from jax.sharding import PartitionSpec as P

    sizes = dict(mesh.shape)
    batch = tuple(a for a in ("data", "fsdp") if a in sizes)
    if q.shape[0] % math.prod(sizes[a] for a in batch):
        batch = ()
    row = P(batch or None)
    return jax.shard_map(kernel, mesh=mesh, in_specs=(row,) * 5,
                         out_specs=row, check_vma=False)(q, k, v, g, beta)
