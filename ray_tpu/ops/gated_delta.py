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
(I + A^4) .. is EXACT after log2(C) factors (`_unit_lower_inverse`);
nothing is approximated.  Every decay is the exp of a DIFFERENCE of running
sums that is <= 0 where it is used, never a quotient of exponentials.

Precision.  The state, A, its inverse and D are float32.  A matmul's
operand that is bfloat16 goes to the MXU as it is; a float32 operand goes
as two bfloat16 parts, its rounding and the rest (`_mxu`: hi x hi + hi x lo
+ lo x hi, an error near 2^-16 of the product), so the state is never
rounded to bfloat16 on its way into a product.  Row scalings are kept
OUTSIDE the products with K and Q (exp(G) (K S), not (exp(G) K) S), so that
bfloat16 keys and queries stay exact operands.  What follows under "The
passes" changes which rows share a product and the order of float32
additions, never what is rounded: every row of a stacked product is the
product the unstacked lines made.

The passes.  A product of parts is two or three MXU passes, and at a chunk
of 64 a pass fills a quarter of the array, so the lines make few and full
ones (`mxu_passes` counts them from the traced lines; the plan says
`passes<fwd>+<bwd>` a chunk a value head: 28.5 + 50.5 at the cell's shapes
where PR 42's lines made 44 + 118).  (1) A doubling of the inverse, T <- T +
T P and P <- P P, is ONE product of [T; P] stacked by rows against P (all of
B = -A's powers commute): six float32 products at C = 64, not ten.  (2)
Products that share an operand are one: [k; q] against k^T (K K^T over Q
K^T, which a key head's value heads share too) and against S; in the
backward [do; dR] against D^T, [gamma dKS; gamma do] against S^T, [X; Y]
against k, and the sums q^T (gamma do) + k^T (gamma dKS) and X^T k + Y^T q
each ONE 128-deep contraction of the stacks; an operand of two products is
split into parts once.  (3) The backward kernel makes a chunk's quantities
once: its walk forward computes no o and leaves the decays, M, T, K S and D
of every chunk in VMEM scratch (`_KEPT`, 112 KB a chunk a head) for its walk
back.  (4) What binds the kernels is not the count but the CHAIN: the
inverse is six products each waiting for the one before (about 230 cycles a
link on a v5e), and a chunk loop that inverts inside its body runs the
chunks' chains one behind the other (PERF.md, PR 43: a forward call took
18.16 ms with ten products, with six, with and without the stacks; 6.8
without the inverse).  So a program first makes ready ALL its block's
chunks, whatever does not wait for the state, as ONE batch
(`_block_ready`: K K^T, the decays, M, A and the inverses of chunks x heads
matrices [n, C, C], every product of a doubling a batched product, so each
matrix's is issued between the others'), leaves T (and P) in VMEM scratch,
and only then walks the state through the chunks (`_chunk_finish`, an
unrolled loop as before).  And a program works `PROGRAM_HEADS` = 2 value
heads of one key head, two state chains the scheduler interleaves, q and k
loaded once for both.  The loop bodies stay ONE chunk: what is traced and
lowered at every compile is no more than PR 42's (a block written out as
straight-line code ran as fast and cost 30 s of every set-up).

The kernels.  Grid (batch x value heads / 2, blocks of time), the second
axis sequential; a block is `BLOCK_CHUNKS` chunks, the float32 states
carried through an unrolled loop and across a head's blocks in a VMEM
scratch.  q and k are read where their projection laid them, [b, t, key
heads x d_k], through an index map at the heads' key head (GQA's repeat
never exists in HBM), v and o as [b, t, value heads x d_v], two heads' lanes
a program.  The running sums G (made outside: a cumulative sum over a
chunk) and beta come a chunk a row, [b x heads, chunks, C].  Under
differentiation the forward also writes the state every block starts from
([b x heads, blocks, d_k, d_v] float32); the backward kernel walks the
blocks in reverse: a block's chunks forward from that state (the two
phases above, no o), then back through them carrying dL/dS
(`_chunk_backward`).  It gives dq and dk a VALUE head (summed over a key
head's group outside), dv, dG (turned into dg by a reverse cumulative sum
over the chunk, outside) and dbeta.  A sequence that is no multiple of the
block is padded with k = 0, beta = 0, g = 0: a padded step leaves the state
alone.

Off TPU: the interpreter when RAY_TPU_PALLAS_INTERPRET=1, else
`gated_delta_xla`, the same chunk lines (one chunk, one head:
`_chunk_forward`) under `vmap` and a scan over checkpointed chunks.
`dispatch.taken()` holds the path under "gated_delta_rule" and the plan
under "gated_delta_rule.plan".
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops import dispatch

F32, BF16 = jnp.float32, jnp.bfloat16
DEFAULT_CHUNK = 64
BLOCK_CHUNKS = 8        # chunks a grid step walks: 512 steps at chunk 64
PROGRAM_HEADS = 2       # value heads of one key head a program works


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
# One chunk: the same lines are the XLA path (2-D arrays under vmap) and the
# kernels' body (on what they load into VMEM), where what does not wait for
# the state takes a block's chunks and heads as axes in front
# ---------------------------------------------------------------------------

def _parts(x):
    """x as the MXU takes it: itself if bfloat16, else its bfloat16
    rounding and what the rounding left (a tuple is parts already)."""
    if isinstance(x, tuple):
        return x
    if x.dtype == BF16:
        return (x,)
    hi = x.astype(BF16)
    return hi, (x - hi.astype(F32)).astype(BF16)


def _mxu(a, b, contract_a: int = 1, contract_b: int = 0):
    """a . b of two matrices over the named axes (of their last two; axes
    before those are a batch the two share), float32: the products of the
    operands' parts but lo x lo.  An operand is an array or, where several
    products share it, its `_parts` made once."""
    total = None
    for i, pa in enumerate(_parts(a)):
        for j, pb in enumerate(_parts(b)):
            if i + j < 2:
                batch = tuple(range(pa.ndim - 2))
                dims = (((len(batch) + contract_a,),
                         (len(batch) + contract_b,)), (batch, batch))
                term = jax.lax.dot_general(pa, pb, dims,
                                           preferred_element_type=F32)
                total = term if total is None else total + term
    return total


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _dot(a, b, contract_a: int = 1, contract_b: int = 0):
    """`_mxu` of two arrays where JAX differentiates the chunk (the XLA
    path): its derivative is the product's, the split has none of its
    own."""
    return _mxu(a, b, contract_a, contract_b)


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
    """[.., 1, n] -> [.., n, 1] without a transpose: the diagonal of its
    broadcast, summed along lanes."""
    n = row.shape[-1]
    i, j = _square_indices(n)
    wide = jnp.broadcast_to(row, row.shape[:-2] + (n, n))
    return jnp.sum(jnp.where(i == j, wide, 0.0), axis=-1, keepdims=True)


def _to_row(column):
    """[n, 1] -> [1, n], likewise, summed along sublanes."""
    n = column.shape[0]
    i, j = _square_indices(n)
    return jnp.sum(jnp.where(i == j, jnp.broadcast_to(column, (n, n)), 0.0),
                   axis=0, keepdims=True)


def _unit_lower_inverse(a):
    """(I + a)^-1 of strictly lower triangular a [.., n, n]: with b = -a,
    the sum of b's powers below n.  T_k (the powers below 2^k) and P_k =
    b^(2^k) commute, so a doubling, T <- T + T P and P <- P P, is ONE
    product of the two stacked by rows against P; the last needs T alone.
    Exact once 2^k reaches n.  A doubling waits for the one before it: a
    batch of matrices is made side by side, each matrix's product between
    the others', which do not wait for it."""
    n = a.shape[-1]
    i, j = _square_indices(n)
    doublings = max(0, math.ceil(math.log2(n)) - 1)
    T = jnp.where(i == j, 1.0, 0.0) - a
    if not doublings:
        return T
    P = _dot(a, a)                                      # b b
    for _ in range(doublings - 1):
        both = _dot(jnp.concatenate([T, P], axis=-2), P)
        T, P = T + both[..., :n, :], both[..., n:, :]
    return T + _dot(T, P)


def _chunk_scalars(G_row, beta_row):
    """A chunk's masks and what its G_row and beta_row [.., 1, C] give as
    columns [.., C, 1]: G, beta, gamma = exp(G), tail = exp(G_C - G)."""
    C = G_row.shape[-1]
    i, j = _square_indices(C)
    G = _to_column(G_row)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1) == C - 1
    G_last = jnp.sum(jnp.where(last, G_row, 0.0), axis=-1, keepdims=True)
    return dict(strict=i > j, lower=i >= j, last=last, G=G,
                beta=_to_column(beta_row), gamma=jnp.exp(G), G_last=G_last,
                tail=jnp.exp(G_last - G))


# what the backward kernel's forward walk leaves of a chunk for its walk back
_KEPT = ("decay", "M", "T", "KS", "D")


def _rows(q, k):
    """k's rows over q's: ONE operand of the products both are in (q None,
    where the state alone is wanted: k's)."""
    return k if q is None else jnp.concatenate([k, q], axis=-2)


def _prepare(q, k, G_row, beta_row):
    """What of a chunk does not wait for its state: -> the scalars with
    decay, M, A = beta M [.., C, C] and (q given) P.  q, k [.., C, d_k];
    G_row and beta_row [.., 1, C] float32 (G the running sum of g inside
    the chunk), with the value heads of k's key head as one more axis in
    front where a kernel prepares them together: K K^T over Q K^T is one
    product, and the heads share it."""
    C = k.shape[-2]
    c = _chunk_scalars(G_row, beta_row)
    lower = c["lower"]
    # exp(G_i - G_j) where i >= j: the difference is <= 0 there
    decay = jnp.where(
        lower, jnp.exp(jnp.where(lower, c["G"] - G_row, 0.0)), 0.0)
    by_k = _dot(_rows(q, k), k, 1, 1)
    M = jnp.where(c["strict"], by_k[..., :C, :] * decay, 0.0)
    c.update(decay=decay, M=M, A=c["beta"] * M)
    if q is not None:
        c["P"] = jnp.where(lower, by_k[..., C:, :] * decay, 0.0)
    return c


def _chunk_finish(c, T, q, k, v, S):
    """The rest of a chunk of one head, 2-D: c its scalars (and P, if o is
    wanted), T = (I + A)^-1, v [C, d_v], S [d_k, d_v] float32 the state the
    chunk starts from; K S over Q S is one product.  Leaves T, KS, D and
    (with q) o [C, d_v] float32 in c.  -> the state after the chunk."""
    C = k.shape[0]
    by_S = _dot(_rows(q, k), S)
    KS = c["gamma"] * by_S[:C]
    D = _dot(T, c["beta"] * (v.astype(F32) - KS))       # v - exp(G) K S
    c.update(T=T, KS=KS, D=D)
    if q is not None:
        c["o"] = c["gamma"] * by_S[C:] + _dot(c["P"], D)
    return _decayed(S, c["G_last"]) + _dot(k, c["tail"] * D, 0, 0)


def _decayed(S, G_last):
    """exp(G_C) S, G_C [1, 1]: broadcast along lanes, then sublanes."""
    return jnp.exp(jnp.broadcast_to(G_last, (1, S.shape[1]))) * S


def _chunk_forward(q, k, v, G_row, beta_row, S):
    """One chunk of one head, 2-D: -> (o [C, d_v] float32, the state after
    the chunk)."""
    c = _prepare(q, k, G_row, beta_row)
    S = _chunk_finish(c, _unit_lower_inverse(c["A"]), q, k, v, S)
    return c["o"], S


def _heads_walk(q, k, vs, G_rows, beta_rows, Ss):
    """A chunk of one key head's value heads walked forward as the kernels
    walk a BLOCK's chunks: all prepared and inverted together (G_rows,
    beta_rows [heads, 1, C]), then each finished from its own scalars.  ->
    a head's (c, the state after the chunk); q None: no o."""
    ready = _prepare(q, k, G_rows, beta_rows)
    Ts = _unit_lower_inverse(ready["A"])
    out = []
    for h, (v, S) in enumerate(zip(vs, Ss)):
        c = _chunk_scalars(G_rows[h], beta_rows[h])
        c.update({name: ready[name][h] for name in ("decay", "M", "P")
                  if name in ready})
        out.append((c, _chunk_finish(c, Ts[h], q, k, v, S)))
    return out


def _chunk_backward(q, k, v, G_row, beta_row, S, kept, do, dS_next):
    """The chunk walked back from do [C, d_v] and dL/dS' [d_k, d_v], with
    what the walk forward kept of it (`_KEPT`): -> (dq, dk [C, d_k], dv [C,
    d_v], dG and dbeta [1, C], dL/dS), float32.  dG is with respect to the
    running sums; the caller sums it back over the chunk's later steps for
    dg.  Nothing differentiates these lines, so the products are `_mxu`'s,
    an operand of two of them in parts made once."""
    C = q.shape[0]
    c = _chunk_scalars(G_row, beta_row)
    strict, lower = c["strict"], c["lower"]
    beta, gamma, tail = c["beta"], c["gamma"], c["tail"]
    decay, M, T, KS, D = (kept[name] for name in _KEPT)
    S_parts, dS_parts = _parts(S), _parts(dS_next)
    rows = _rows(q, k)
    P = jnp.where(lower, _mxu(q, k, 1, 1) * decay, 0.0)
    QS = gamma * _mxu(q, S_parts)

    # o = QS + P D;  S' = exp(G_C) S + K^T (tail D)
    K_dS = _mxu(k, dS_parts)                            # [C, d_v]
    dD = _mxu(P, do, 0, 0) + tail * K_dS
    g_do = gamma * do
    dG = jnp.sum(do * QS, axis=1, keepdims=True)        # [C, 1]
    # D = T (beta rest), T = (I + A)^-1;  do over dR against D^T
    dR = _mxu(T, dD, 0, 0)
    by_D = _mxu(jnp.concatenate([do.astype(F32), dR]), D, 1, 1)
    dP = jnp.where(lower, by_D[:C], 0.0)
    dA = -jnp.where(strict, by_D[C:], 0.0)
    dv = beta * dR
    dbeta = (jnp.sum(dR * (v - KS), axis=1, keepdims=True)
             + jnp.sum(dA * M, axis=1, keepdims=True))
    # rest = v - KS, KS = gamma (K S): with QS = gamma (Q S), the rows
    # that meet S, k's over q's like `rows`
    met_S = _parts(jnp.concatenate([gamma * (0.0 - dv), g_do]))
    by_S = _mxu(met_S, S_parts, 1, 1)                   # dk over dq
    dS = _decayed(dS_next, c["G_last"]) + _mxu(rows, met_S, 0, 0)
    dG = dG - jnp.sum(dv * KS, axis=1, keepdims=True)
    # A = beta M, M = (K K^T) decay;  P = (Q K^T) decay: X over Y
    XY = _parts(jnp.concatenate([beta * dA * decay, dP * decay]))
    by_k = _mxu(XY, k)                                  # X K over Y K
    dk = by_S[:C] + by_k[:C] + _mxu(XY, rows, 0, 0)     # X^T K + Y^T Q
    dq = by_S[C:] + by_k[C:]
    Z = dA * (beta * M) + dP * P            # d(decay) x decay, elementwise
    dG = dG + jnp.sum(Z, axis=1, keepdims=True)
    dG_row = 0.0 - jnp.sum(Z, axis=0, keepdims=True)
    # S' again: tail = exp(G_C - G) on D's rows, exp(G_C) on S
    tD = tail * D
    dk = dk + _mxu(tD, dS_parts, 1, 1)
    d_tail = jnp.sum(tD * K_dS, axis=1, keepdims=True)  # x tail already
    dG = dG - d_tail
    to_last = (jnp.sum(d_tail, axis=0, keepdims=True)
               + jnp.exp(c["G_last"]) * jnp.sum(
                   jnp.sum(dS_next * S, axis=1, keepdims=True),
                   axis=0, keepdims=True))              # [1, 1]
    dG_row = dG_row + _to_row(dG) + jnp.where(c["last"], to_last, 0.0)
    return dq, dk, dv, dG_row, _to_row(dbeta), dS


def _abstract_chunk(chunk: int, dk: int, dv: int, qk_dtype, v_dtype):
    """(q, k, v, G_row, beta_row, S) of a chunk as shapes, and what the
    backward kernel keeps of it (`_KEPT`)."""
    sds = jax.ShapeDtypeStruct
    ops = (sds((chunk, dk), qk_dtype), sds((chunk, dk), qk_dtype),
           sds((chunk, dv), v_dtype), sds((1, chunk), F32),
           sds((1, chunk), F32), sds((dk, dv), F32))

    def kept(k, v, G_row, beta_row, S):
        (c, _), = _heads_walk(None, k, [v], G_row[None], beta_row[None], [S])
        return {name: c[name] for name in _KEPT}

    return ops, jax.eval_shape(kept, *ops[1:])


def _count_dots(jaxpr) -> int:
    """The MXU passes of a jaxpr and of the jaxprs its equations hold: its
    dot_generals, a batched one as many as its batch."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            n += math.prod(eqn.outvars[0].aval.shape[:-2])
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count_dots(sub)
    return n


@functools.lru_cache(maxsize=None)
def mxu_passes(chunk: int, dk: int, dv: int, qk_dtype, v_dtype, heads: int):
    """(forward, backward): the MXU passes of the two kernels' bodies a
    chunk a value head, counted from the chunk lines as traced at these
    widths for the `heads` value heads a program works; the backward's is
    its walk forward and its walk back.  The plan's `passes<fwd>+<bwd>`."""
    (q, k, v, row, _, S), kept = _abstract_chunk(chunk, dk, dv, qk_dtype,
                                                 v_dtype)
    rows = jax.ShapeDtypeStruct((heads,) + row.shape, F32)

    def count(f, *args):
        return _count_dots(jax.make_jaxpr(f)(*args).jaxpr)

    def walk(with_q):
        def f(q, k, v, rows, S):
            return [(c.get("o"), S) for c, S in _heads_walk(
                q if with_q else None, k, [v] * heads, rows, rows,
                [S] * heads)]
        return count(f, q, k, v, rows, S) / heads

    return walk(True), walk(False) + count(_chunk_backward, q, k, v, row,
                                           row, S, kept, v, S)


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

def _loader(refs, chunk: int, heads: int):
    """-> (rows, operands): rows(c) chunk c's rows of a block, operands(c,
    h) its (q, k, head h's v, G_row, beta_row) of a program's (q_ref,
    k_ref, v_ref, G_ref, beta_ref); c may be traced."""
    from jax.experimental import pallas as pl

    q_ref, k_ref, v_ref, G_ref, beta_ref = refs
    dv = v_ref.shape[1] // heads

    def rows(c):
        return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)

    def operands(c, h):
        return (q_ref[rows(c), :], k_ref[rows(c), :],
                v_ref[rows(c), h * dv:(h + 1) * dv],
                G_ref[h, pl.ds(c, 1), :], beta_ref[h, pl.ds(c, 1), :])

    return rows, operands


def _block_ready(refs, chunk: int, with_q: bool):
    """Every chunk of a program's block, every head, prepared and inverted
    TOGETHER ([heads, chunks, C, C] each): -> `_prepare`'s dict with T."""
    q_ref, k_ref, _, G_ref, beta_ref = refs
    heads, chunks, _ = G_ref.shape
    k = k_ref[...].reshape(chunks, chunk, -1)
    q = q_ref[...].reshape(k.shape) if with_q else None
    c = _prepare(q, k, G_ref[...][:, :, None, :], beta_ref[...][:, :, None, :])
    c["T"] = _unit_lower_inverse(
        c["A"].reshape(heads * chunks, chunk, chunk)).reshape(c["A"].shape)
    return c


def _fwd_kernel(q_ref, k_ref, v_ref, G_ref, beta_ref, o_ref, *rest,
                chunk: int, chunks: int, heads: int, save_states: bool):
    from jax.experimental import pallas as pl

    if save_states:
        first_ref, S_ref, T_ref, P_ref = rest
    else:
        S_ref, T_ref, P_ref = rest
    refs = (q_ref, k_ref, v_ref, G_ref, beta_ref)
    dv = v_ref.shape[1] // heads

    @pl.when(pl.program_id(1) == 0)
    def _():
        S_ref[...] = jnp.zeros_like(S_ref)

    if save_states:
        first_ref[...] = S_ref[...]     # the states this block starts from

    # 1. what does not wait for the state, the whole block at once
    ready = _block_ready(refs, chunk, True)
    T_ref[...], P_ref[...] = ready["T"], ready["P"]

    # 2. the states through the chunks
    rows, operands = _loader(refs, chunk, heads)

    def one_chunk(c, S):
        S = list(S)
        for h in range(heads):
            q, k, v, G_row, beta_row = operands(c, h)
            of = _chunk_scalars(G_row, beta_row)
            of["P"] = P_ref[h, c]
            S[h] = _chunk_finish(of, T_ref[h, c], q, k, v, S[h])
            o_ref[rows(c), h * dv:(h + 1) * dv] = of["o"].astype(o_ref.dtype)
        return tuple(S)

    S = jax.lax.fori_loop(0, chunks, one_chunk,
                          tuple(S_ref[h] for h in range(heads)),
                          unroll=True)
    for h in range(heads):
        S_ref[h] = S[h]


def _bwd_kernel(q_ref, k_ref, v_ref, G_ref, beta_ref, do_ref, first_ref,
                dq_ref, dk_ref, dv_ref, dG_ref, dbeta_ref,
                dS_ref, states_ref, *kept_refs, chunk: int, chunks: int,
                heads: int):
    from jax.experimental import pallas as pl

    refs = (q_ref, k_ref, v_ref, G_ref, beta_ref)
    kept_refs = dict(zip(_KEPT, kept_refs))
    dk, dv = q_ref.shape[1], v_ref.shape[1] // heads
    rows, operands = _loader(refs, chunk, heads)

    @pl.when(pl.program_id(1) == 0)         # the LAST block in time
    def _():
        dS_ref[...] = jnp.zeros_like(dS_ref)

    # 1. forward through the block's chunks, no o: what does not wait for
    # the state at once, then each chunk's first state, KS and D; `_KEPT`
    # stays in VMEM
    ready = _block_ready(refs, chunk, False)
    for name in ("decay", "M", "T"):
        kept_refs[name][...] = ready[name]

    def again(c, S):
        S = list(S)
        for h in range(heads):
            states_ref[h, c] = S[h]
            _, k, v, G_row, beta_row = operands(c, h)
            of = _chunk_scalars(G_row, beta_row)
            S[h] = _chunk_finish(of, kept_refs["T"][h, c], None, k, v, S[h])
            kept_refs["KS"][h, c], kept_refs["D"][h, c] = of["KS"], of["D"]
        return tuple(S)

    jax.lax.fori_loop(0, chunks, again,
                      tuple(first_ref[h] for h in range(heads)),
                      unroll=True)

    # 2. back through the chunks, dL/dS carried
    def back(i, dS):
        c = chunks - 1 - i
        dS = list(dS)
        for h in range(heads):
            keys, values = slice(h * dk, (h + 1) * dk), \
                slice(h * dv, (h + 1) * dv)
            dq, dk_, dv_, dG, dbeta, dS[h] = _chunk_backward(
                *operands(c, h), states_ref[h, c],
                {name: ref[h, c] for name, ref in kept_refs.items()},
                do_ref[rows(c), values], dS[h])
            dq_ref[rows(c), keys] = dq.astype(dq_ref.dtype)
            dk_ref[rows(c), keys] = dk_.astype(dk_ref.dtype)
            dv_ref[rows(c), values] = dv_.astype(dv_ref.dtype)
            dG_ref[h, pl.ds(c, 1), :] = dG
            dbeta_ref[h, pl.ds(c, 1), :] = dbeta
        return tuple(dS)

    dS = jax.lax.fori_loop(0, chunks, back,
                           tuple(dS_ref[h] for h in range(heads)),
                           unroll=True)
    for h in range(heads):
        dS_ref[h] = dS[h]


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=48 << 20)


def _program_heads(shapes) -> int:
    """The value heads a program works: `PROGRAM_HEADS` of one key head
    where its group divides so, else one."""
    hv, hk = shapes[:2]
    return PROGRAM_HEADS if (hv // hk) % PROGRAM_HEADS == 0 else 1


def _specs(shapes, chunk: int, chunks: int, block_of):
    """The BlockSpecs both kernels share, for program (p, i) working the
    time block `block_of(i)` of `_program_heads` value heads, p counting
    such sets through batch x value heads: q and k at their KEY head, v
    (and o, do, dv) at the heads, dq and dk a value head, the chunk rows
    of G and beta, a block's first states."""
    from jax.experimental import pallas as pl

    hv, hk, dk, dv = shapes
    heads = _program_heads(shapes)
    rows, group, sets = chunk * chunks, hv // hk, hv // heads
    key = pl.BlockSpec((None, rows, dk),
                       lambda p, i: (p // sets, block_of(i),
                                     (p % sets) * heads // group))
    per_value_head = pl.BlockSpec(
        (None, rows, heads * dk),
        lambda p, i: (p // sets, block_of(i), p % sets))
    value = pl.BlockSpec((None, rows, heads * dv),
                         lambda p, i: (p // sets, block_of(i), p % sets))
    scalars = pl.BlockSpec((heads, chunks, chunk),
                           lambda p, i: (p, block_of(i), 0))
    state = pl.BlockSpec((heads, None, dk, dv),
                         lambda p, i: (p, block_of(i), 0, 0))
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
    heads = _program_heads(shapes)
    chunks = min(BLOCK_CHUNKS, t // chunk)
    blocks = t // (chunk * chunks)
    key, _, value, scalars, state = _specs(shapes, chunk, chunks, lambda i: i)
    out_specs, out_shape = [value], [jax.ShapeDtypeStruct(v3.shape, v3.dtype)]
    if save_states:
        out_specs.append(state)
        out_shape.append(jax.ShapeDtypeStruct((b * hv, blocks, dk, dv), F32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, chunks=chunks,
                          heads=heads, save_states=save_states),
        grid=(b * hv // heads, blocks),
        in_specs=[key, key, value, scalars, scalars],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), F32)] + [
            pltpu.VMEM((heads, chunks, chunk, chunk), F32)] * 2,
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
    heads = _program_heads(shapes)
    chunks = min(BLOCK_CHUNKS, t // chunk)
    blocks = t // (chunk * chunks)
    key, per_value_head, value, scalars, state = _specs(
        shapes, chunk, chunks, lambda i: blocks - 1 - i)
    wide = jax.ShapeDtypeStruct((b, t, hv * dk), q3.dtype)
    kept = _abstract_chunk(chunk, dk, dv, q3.dtype, v3.dtype)[1]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, chunks=chunks,
                          heads=heads),
        grid=(b * hv // heads, blocks),
        in_specs=[key, key, value, scalars, scalars, value, state],
        out_specs=[per_value_head, per_value_head, value, scalars, scalars],
        out_shape=[wide, wide, jax.ShapeDtypeStruct(v3.shape, v3.dtype),
                   jax.ShapeDtypeStruct(G.shape, F32),
                   jax.ShapeDtypeStruct(G.shape, F32)],
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), F32),
                        pltpu.VMEM((heads, chunks, dk, dv), F32)] + [
            pltpu.VMEM((heads, chunks) + kept[name].shape, F32)
            for name in _KEPT],
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


# What a caller's `jax.checkpoint` may keep of the rule (`save_only_these_
# names`): with o and the blocks' first states kept, its backward runs no
# forward kernel again, only whatever makes q, k and v.
KEPT_NAMES = ("gated_delta_out", "gated_delta_states")


def _rule_vjp_fwd(q3, k3, v3, G, beta, shapes, chunk):
    o, first = _rule_fwd(q3, k3, v3, G, beta, shapes, chunk, True)
    o, first = (checkpoint_name(a, n) for a, n in zip((o, first), KEPT_NAMES))
    return o, (q3, k3, v3, G, beta, first)


def over_group(d, value_heads: int, key_heads: int):
    """d [b, t, value heads x d_k], a cotangent a VALUE head -> [b, t, key
    heads x d_k]: a key head's value heads summed in float32, BY WHOLE
    TILES (`common.by_tiles`: the group a major axis, so the sum is tile
    adds; the [b, t, key heads, group, d_k] view is re-laid before a sum)."""
    from ray_tpu.models import common

    tiles = common.by_tiles(d, value_heads)     # [b, t / 8, hv, 8, d_k]
    b, blocks, _, rows, width = tiles.shape
    with jax.named_scope(common.SSM_CHAIN):
        summed = jnp.sum(
            tiles.reshape(b, blocks, key_heads, value_heads // key_heads,
                          rows, width), axis=3, dtype=F32)
        return common.from_tiles(summed.astype(d.dtype))


def _rule_vjp_bwd(shapes, chunk, res, do3):
    q3, k3, v3, G, beta, first = res
    hv, hk = shapes[:2]
    dq, dk, dv, dG, dbeta = _rule_bwd(q3, k3, v3, G, beta, do3, first,
                                      shapes, chunk)
    return over_group(dq, hv, hk), over_group(dk, hv, hk), dv, dG, dbeta


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
    forward, backward = mxu_passes(
        chunk, k.shape[-1], v.shape[-1], jnp.dtype(k.dtype),
        jnp.dtype(v.dtype), _program_heads((hv, hk)))
    dispatch.record("gated_delta_rule.plan",
                    f"chunk{chunk},heads{hv}over{hk},dk{k.shape[-1]},"
                    f"dv{v.shape[-1]},state_f32,bwd_pallas,"
                    f"passes{forward:g}+{backward:g}")

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
