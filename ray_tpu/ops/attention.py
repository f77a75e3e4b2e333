"""Attention ops: Pallas TPU flash attention (fwd + bwd) with a jnp fallback.

The reference framework ships no attention kernels (SURVEY.md §5 — long-context
machinery is absent in-tree); on TPU this is a core op.  Design:

  - `flash_attention(q, k, v, causal=...)`: online-softmax tiled kernel
    (Pallas, grid over (batch*heads, q-blocks), fori_loop over k-blocks) so
    the s×s score matrix never materializes in HBM.
  - `flash_attention_chunk(...)`: the offset-aware variant returning
    (out, lse) — the building block ring attention uses per K/V chunk
    (ops/ring_attention.py); positions enter as DYNAMIC scalars so the
    same compiled kernel serves every ring step.
  - Backward: Pallas dq and dk/dv kernels recomputing scores blockwise
    from the saved logsumexp (standard flash backward — dq grid over
    q-blocks, dkv grid over k-blocks); the s×s matrix never exists in
    the backward either.  The lse OUTPUT is differentiable too (ring
    attention's merge weights depend on it): ds += p * dlse.
  - CPU / odd-shape fallback: `attention_reference` with identical
    semantics — the numerical ground truth in tests (which compare both
    paths in interpret mode, values and grads).

Layout convention: q, k, v are [batch, seq, heads, head_dim] (the models/
convention); kernels internally fold batch×heads.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops import dispatch

_NEG_INF = float(-1e30)


def _can_use_pallas(seq_q: int, seq_k: int, head_dim: int,
                    block_q: int, block_k: int) -> bool:
    if dispatch.interpret_mode():
        return seq_q % block_q == 0 and seq_k % block_k == 0
    return (
        dispatch.platform() == "tpu"
        and seq_q % block_q == 0
        and seq_k % block_k == 0
        and head_dim % 64 == 0
    )


# ---------------------------------------------------------------------------
# Reference (jnp) path — also the numerical ground truth in tests.
# ---------------------------------------------------------------------------

def attention_reference(q, k, v, causal: bool = True,
                        sm_scale: Optional[float] = None):
    """Plain attention. q:[b,s,h,d] k,v:[b,t,h,d] -> [b,s,h,d]."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        # Align ends: query i attends keys j where j - (sk - sq) <= i.
        mask = (jnp.arange(sk)[None, :] - (sk - sq)
                <= jnp.arange(sq)[:, None])
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# Pallas forward kernel (offset-aware, emits logsumexp)
# ---------------------------------------------------------------------------
# Scalar-prefetch arg offs = [q_off, kv_off]: global position of this
# operand's row/col 0.  The plain causal call uses (sk - sq, 0) (ends
# aligned); ring attention passes each chunk's global offsets, so one
# compiled kernel serves every ring step (fully-unmasked, diagonal, and
# fully-masked chunks alike).

def _fwd_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                causal: bool, block_q: int, block_k: int, seq_k: int,
                sm_scale: float):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    # Keep q in its NATIVE dtype: on TPU a bf16×bf16 matmul with f32
    # accumulation runs the MXU at full rate, while upcasting inputs to
    # f32 forces the multi-pass f32 path (~3-6× slower).  sm_scale is
    # applied to the f32 scores after the matmul instead.
    q = q_ref[0]  # [block_q, d]
    d = q.shape[-1]

    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)

    num_k_blocks = seq_k // block_k
    if causal:
        q_off = offs_ref[0]
        kv_off = offs_ref[1]
        # Last k-block any row of this q-block may attend to:
        # col <= q_off - kv_off + row_max.  floor_divide (NOT lax.div,
        # which truncates toward zero) so negative row_max yields hi=0.
        row_max = q_off - kv_off + (qi + 1) * block_q - 1
        hi = jnp.clip(jnp.floor_divide(row_max, block_k) + 1,
                      0, num_k_blocks)
    else:
        hi = num_k_blocks

    def body(j, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        # [block_q, block_k] f32
        if causal:
            rows = offs_ref[0] + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = offs_ref[1] + j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # p in v's dtype for the second MXU matmul (f32 accumulation
        # preserved by preferred_element_type) — same as every
        # production flash kernel; probabilities are in [0, 1] so bf16
        # rounding here is benign relative to the softmax itself.
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, hi, body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-30)
    # Rows with no visible keys (possible in ring chunks "from the
    # future"): m stayed at -inf, so p accumulated exp(0)=1 garbage —
    # zero the output and mark lse = -inf ("no weight" for the merge).
    valid = m > _NEG_INF / 2
    o_ref[0] = jnp.where(valid, acc / l_safe, 0.0).astype(o_ref.dtype)
    lse = jnp.where(valid & (l > 0), m + jnp.log(l_safe), _NEG_INF)
    # lse is logically [block_q]; stored broadcast over an 8-sublane axis so
    # the block shape ends in (8, block_q) per Mosaic's tiling constraint.
    lse_ref[0] = jnp.broadcast_to(lse[:, 0][None, :], (8, block_q))


def _flash_fwd(q, k, v, offs, causal: bool, sm_scale: float,
               block_q: int, block_k: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    sk = k.shape[1]
    # fold batch*heads, put seq in the middle: [bh, s, d]
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)

    grid = (b * h, sq // block_q)
    kernel = functools.partial(
        _fwd_kernel, causal=causal, block_q=block_q, block_k=block_k,
        seq_k=sk, sm_scale=sm_scale)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda bh, i, offs: (bh, i, 0)),
                pl.BlockSpec((1, sk, d), lambda bh, i, offs: (bh, 0, 0)),
                pl.BlockSpec((1, sk, d), lambda bh, i, offs: (bh, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda bh, i, offs: (bh, i, 0)),
                pl.BlockSpec((1, 8, block_q), lambda bh, i, offs: (bh, 0, i)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 8, sq), jnp.float32),
        ],
        interpret=dispatch.interpret_mode(),
        name="flash_fwd",
    )(offs, qf, kf, vf)
    out = out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    return out, lse[:, 0, :]  # lse: [bh, sq]


# ---------------------------------------------------------------------------
# Pallas backward kernels: recompute-by-block using the saved logsumexp.
# Standard flash backward split (the reference design point is the public
# flash-attention algorithm, not the Ray repo): dq iterates k-blocks per
# q-block; dk/dv iterate q-blocks per k-block.  delta = rowsum(do * out)
# is precomputed outside; dlse is the cotangent of the lse OUTPUT (zero
# for plain flash_attention, nonzero under ring attention's merge).
# ---------------------------------------------------------------------------

def _bwd_recompute_p(q, k, lse_row, rows, cols, causal, sm_scale):
    """Shared score recompute: p_ij = exp(q·k·scale - lse_i), masked."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    p = jnp.exp(s - lse_row[:, None])
    if causal:
        p = jnp.where(cols <= rows, p, 0.0)
    return p


def _bwd_dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dlse_ref, dq_ref, *, causal: bool,
                   block_q: int, block_k: int, seq_k: int, sm_scale: float):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    q = q_ref[0]                              # [block_q, d] native dtype
    do = do_ref[0]                            # [block_q, d] native dtype
    lse = lse_ref[0, 0, :]                    # [block_q]
    # (delta + (-dlse)) enters every column uniformly: fold into one term.
    corr = delta_ref[0, 0, :] - dlse_ref[0, 0, :]  # [block_q]
    d = q.shape[-1]

    num_k_blocks = seq_k // block_k
    if causal:
        row_max = offs_ref[0] - offs_ref[1] + (qi + 1) * block_q - 1
        hi = jnp.clip(jnp.floor_divide(row_max, block_k) + 1,
                      0, num_k_blocks)
    else:
        hi = num_k_blocks

    def body(j, dq):
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :]
        if causal:
            rows = offs_ref[0] + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = offs_ref[1] + j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
        else:
            rows = cols = None
        p = _bwd_recompute_p(q, k_blk, lse, rows, cols, causal, sm_scale)
        dp = jax.lax.dot_general(                  # do · v^T
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # [block_q, block_k]
        ds = p * (dp - corr[:, None]) * sm_scale
        return dq + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, hi, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dlse_ref, dk_ref, dv_ref, *, causal: bool,
                    block_q: int, block_k: int, seq_q: int, sm_scale: float):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    k = k_ref[0]                              # [block_k, d] native dtype
    v = v_ref[0]
    d = k.shape[-1]

    num_q_blocks = seq_q // block_q
    if causal:
        # First q-block whose last row can see this k-block's first col.
        lo = jnp.clip(
            jnp.floor_divide(offs_ref[1] + ki * block_k - offs_ref[0],
                             block_q),
            0, num_q_blocks)
    else:
        lo = 0

    def body(j, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(j * block_q, block_q), :]
        do_blk = do_ref[0, pl.ds(j * block_q, block_q), :]
        lse_blk = lse_ref[0, 0, pl.ds(j * block_q, block_q)]
        corr = (delta_ref[0, 0, pl.ds(j * block_q, block_q)]
                - dlse_ref[0, 0, pl.ds(j * block_q, block_q)])
        if causal:
            rows = offs_ref[0] + j * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = offs_ref[1] + ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
        else:
            rows = cols = None
        p = _bwd_recompute_p(q_blk, k, lse_blk, rows, cols, causal,
                             sm_scale)                 # [block_q, block_k]
        dv_new = dv + jax.lax.dot_general(             # p^T · do
            p.astype(do_blk.dtype), do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [block_k, d]
        dp = jax.lax.dot_general(                      # do · v^T
            do_blk, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - corr[:, None]) * sm_scale
        dk_new = dk + jax.lax.dot_general(             # ds^T · q
            ds.astype(q_blk.dtype), q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_new, dv_new

    dk, dv = jax.lax.fori_loop(
        lo, num_q_blocks, body,
        (jnp.zeros((block_k, d), jnp.float32),
         jnp.zeros((block_k, d), jnp.float32)))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _lse8(x, bh, s):
    """[bh, s] f32 -> [bh, 8, s] sublane-broadcast (Mosaic tiling)."""
    return jnp.broadcast_to(x[:, None, :], (bh, 8, s))


def _flash_bwd(q, k, v, out, lse, offs, dout, dlse, causal, sm_scale,
               block_q, block_k):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    sk = k.shape[1]
    bh = b * h
    qf = q.transpose(0, 2, 1, 3).reshape(bh, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(bh, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(bh, sk, d)
    dof = dout.transpose(0, 2, 1, 3).reshape(bh, sq, d)
    delta = jnp.sum(dof.astype(jnp.float32)
                    * out.transpose(0, 2, 1, 3).reshape(bh, sq, d)
                    .astype(jnp.float32), axis=-1)      # [bh, sq]
    lse8 = _lse8(lse, bh, sq)
    delta8 = _lse8(delta, bh, sq)
    dlse8 = _lse8(dlse.astype(jnp.float32), bh, sq)

    seq_spec = pl.BlockSpec((1, 8, sq), lambda g, i, offs: (g, 0, 0))
    full_q = pl.BlockSpec((1, sq, d), lambda g, i, offs: (g, 0, 0))
    full_k = pl.BlockSpec((1, sk, d), lambda g, i, offs: (g, 0, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, block_q=block_q,
                          block_k=block_k, seq_k=sk, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, sq // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda g, i, offs: (g, i, 0)),
                full_k, full_k,
                pl.BlockSpec((1, block_q, d), lambda g, i, offs: (g, i, 0)),
                pl.BlockSpec((1, 8, block_q), lambda g, i, offs: (g, 0, i)),
                pl.BlockSpec((1, 8, block_q), lambda g, i, offs: (g, 0, i)),
                pl.BlockSpec((1, 8, block_q), lambda g, i, offs: (g, 0, i)),
            ],
            out_specs=pl.BlockSpec((1, block_q, d),
                                   lambda g, i, offs: (g, i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=dispatch.interpret_mode(),
        name="flash_bwd_dq",
    )(offs, qf, kf, vf, dof, lse8, delta8, dlse8)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, block_q=block_q,
                          block_k=block_k, seq_q=sq, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, sk // block_k),
            in_specs=[
                full_q,
                pl.BlockSpec((1, block_k, d), lambda g, i, offs: (g, i, 0)),
                pl.BlockSpec((1, block_k, d), lambda g, i, offs: (g, i, 0)),
                full_q, seq_spec, seq_spec, seq_spec,
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, d), lambda g, i, offs: (g, i, 0)),
                pl.BlockSpec((1, block_k, d), lambda g, i, offs: (g, i, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        interpret=dispatch.interpret_mode(),
        name="flash_bwd_dkv",
    )(offs, qf, kf, vf, dof, lse8, delta8, dlse8)

    dq = dq.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    dk = dk.reshape(b, h, sk, d).transpose(0, 2, 1, 3)
    dv = dv.reshape(b, h, sk, d).transpose(0, 2, 1, 3)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom VJP over (out, lse)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_lse(q, k, v, offs, causal, sm_scale, block_q, block_k):
    return _flash_fwd(q, k, v, offs, causal, sm_scale, block_q, block_k)


def _flash_lse_fwd(q, k, v, offs, causal, sm_scale, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, offs, causal, sm_scale, block_q, block_k)
    # Named residuals: under jax.checkpoint with
    # save_only_these_names("attn_out", "attn_lse") (the transformer's
    # "save_attn" remat policy) the kernel outputs are kept from the
    # primal pass, so the backward never re-runs the forward kernel —
    # q/k/v residuals are cheap projections the remat re-derives.
    from jax.ad_checkpoint import checkpoint_name

    q_r = checkpoint_name(q, "attn_q")
    k_r = checkpoint_name(k, "attn_k")
    v_r = checkpoint_name(v, "attn_v")
    out_r = checkpoint_name(out, "attn_out")
    lse_r = checkpoint_name(lse, "attn_lse")
    return (out, lse), (q_r, k_r, v_r, out_r, lse_r, offs)


def _flash_lse_bwd(causal, sm_scale, block_q, block_k, res, cts):
    q, k, v, out, lse, offs = res
    dout, dlse = cts
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, offs, dout, dlse,
                            causal, sm_scale, block_q, block_k)
    return dq, dk, dv, None  # offs (int positions) has no gradient


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def flash_attention_chunk(q, k, v, q_off, kv_off, causal: bool = True,
                          sm_scale: Optional[float] = None,
                          block_q: int = 128, block_k: int = 128):
    """Offset-aware flash attention returning (out, lse).

    q_off / kv_off: GLOBAL position of q[:,0] / k[:,0] (may be traced —
    ring attention passes per-device values).  lse is [b*h, sq] float32;
    rows with no visible keys get lse = -inf (merge-neutral).
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    offs = jnp.stack([jnp.asarray(q_off, jnp.int32),
                      jnp.asarray(kv_off, jnp.int32)])
    return _flash_lse(q, k, v, offs, causal, sm_scale, block_q, block_k)


def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512):
    """Tiled attention. q:[b,s,h,d], k/v:[b,t,h,d] -> [b,s,h,d].

    Uses the Pallas kernels on TPU (or in interpret mode for tests); falls
    back to the jnp reference elsewhere.  Heads must already be expanded
    (GQA repeat happens in the model).  When sq < sk the windows are
    end-aligned (decode convention), matching attention_reference.

    Under an ambient multi-device mesh (jax.sharding.set_mesh) the kernel
    runs per shard inside a shard_map — batch over the data/fsdp axes,
    heads over the tensor axis (parallel/sharding.DEFAULT_RULES): GSPMD
    cannot partition a Mosaic kernel itself.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    sq, sk, d = q.shape[1], k.shape[1], q.shape[-1]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    if not _can_use_pallas(sq, sk, d, bq, bk):
        dispatch.record("flash_attention", "xla")
        return attention_reference(q, k, v, causal, sm_scale)
    dispatch.record("flash_attention", "interpret"
                    if dispatch.interpret_mode() else "pallas")

    def kernel(q, k, v):
        out, _ = flash_attention_chunk(
            q, k, v, sk - sq, 0, causal=causal, sm_scale=sm_scale,
            block_q=bq, block_k=bk)
        return out

    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or mesh.size == 1:
        return kernel(q, k, v)
    from jax.sharding import PartitionSpec as P

    # An axis shards a dim only where it divides it; otherwise that dim
    # is computed replicated (small eval batches on a wide mesh).
    sizes = dict(mesh.shape)
    batch = tuple(a for a in ("data", "fsdp") if a in sizes)
    if q.shape[0] % math.prod(sizes[a] for a in batch):
        batch = ()
    heads = "tensor" if ("tensor" in sizes
                         and q.shape[2] % sizes["tensor"] == 0) else None
    spec = P(batch or None, None, heads, None)
    return jax.shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
