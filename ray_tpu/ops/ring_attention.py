"""Ring attention: exact causal attention over a sequence-sharded mesh axis.

Greenfield capability (SURVEY.md §5 — the reference has no sequence/context
parallelism in-tree; §2.4 mandates it as a first-class mesh axis).  Design
follows Liu et al.'s ring attention (PAPERS.md): each device holds a query
chunk and a rotating key/value chunk; K/V travel around the ring via
`jax.lax.ppermute` while online-softmax statistics (out, logsumexp)
accumulate — the full s×s score matrix never exists, and the per-step
block compute overlaps the ICI transfer (XLA pipelines ppermute with the
einsums).

Two entry points:
  - `ring_attention_sharded(q, k, v, axis_name, causal)`: collective form,
    call inside shard_map/pmap with a named sequence axis.
  - `ring_attention(q, k, v, mesh, causal)`: jit-level wrapper that
    shard_maps over the mesh's "seq" axis (data/tensor axes stay sharded,
    everything else replicated).

Layout: q, k, v are [batch, seq_local, heads, head_dim] (models/
convention, GQA pre-expanded by the caller).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_NEG_INF = float(-1e30)


def _chunk_attention(q, k, v, mask, sm_scale) -> Tuple[jax.Array, jax.Array]:
    """Attention of q against one K/V chunk.

    Returns (out, lse): out [b,sq,h,hd] normalized within the chunk,
    lse [b,h,sq] the chunk's logsumexp — the merge statistics of
    flash/blockwise attention.
    """
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    logits = logits * sm_scale
    if mask is not None:
        logits = jnp.where(mask, logits, _NEG_INF)
    m = jnp.max(logits, axis=-1)                      # [b,h,q]
    # fully-masked rows: keep exp() finite, lse = -inf marks "no weight"
    m_safe = jnp.where(m <= _NEG_INF / 2, 0.0, m)
    p = jnp.exp(logits - m_safe[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    denom = jnp.sum(p, axis=-1)                       # [b,h,q]
    lse = jnp.where(
        denom > 0, m_safe + jnp.log(jnp.maximum(denom, 1e-30)), _NEG_INF)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    out = out / jnp.maximum(denom, 1e-30).transpose(0, 2, 1)[..., None]
    return out, lse


def _merge(o1, l1, o2, l2):
    """Merge two partial attention results by their logsumexps."""
    l_max = jnp.maximum(l1, l2)
    l_max_safe = jnp.where(l_max <= _NEG_INF / 2, 0.0, l_max)
    w1 = jnp.exp(l1 - l_max_safe)
    w2 = jnp.exp(l2 - l_max_safe)
    denom = jnp.maximum(w1 + w2, 1e-30)
    # broadcast [b,h,q] weights onto [b,q,h,d]
    def bc(w):
        return w.transpose(0, 2, 1)[..., None]

    out = (o1 * bc(w1) + o2 * bc(w2)) / bc(denom)
    lse = jnp.where(
        jnp.maximum(l1, l2) <= _NEG_INF / 2,
        _NEG_INF,
        l_max_safe + jnp.log(denom))
    return out, lse


def ring_attention_sharded(q, k, v, axis_name: str, causal: bool = True,
                           sm_scale: Optional[float] = None,
                           block_q: int = 128, block_k: int = 128):
    """Collective ring attention; call inside shard_map over ``axis_name``.

    q, k, v: [b, s_local, h, hd] — this device's sequence chunk.

    The per-step chunk op is the offset-aware Pallas flash kernel
    (ops/attention.py flash_attention_chunk) whenever shapes allow: the
    s_local×s_local score block then never materializes in HBM, in
    forward OR backward (the kernel's custom VJP recomputes by block
    from the saved lse).  Global positions enter the kernel as dynamic
    scalars, so one compiled program serves every ring step.
    """
    from ray_tpu.ops.attention import _can_use_pallas, flash_attention_chunk

    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    b, s_loc, h, hd = q.shape

    bq, bk = min(block_q, s_loc), min(block_k, s_loc)
    use_flash = _can_use_pallas(s_loc, s_loc, hd, bq, bk)
    q_pos = my * s_loc + jnp.arange(s_loc)            # global q positions

    o = jnp.zeros((b, s_loc, h, hd), jnp.float32)
    lse = jnp.full((b, h, s_loc), _NEG_INF, jnp.float32)

    # perm: chunk travels to the next device each step (ring)
    perm = [(i, (i + 1) % n) for i in range(n)]

    k_cur, v_cur = k, v
    for step in range(n):
        # after `step` rotations this device holds the chunk that started
        # on device (my - step) mod n
        src = (my - step) % n
        if use_flash:
            o_c, lse_flat = flash_attention_chunk(
                q, k_cur, v_cur, my * s_loc, src * s_loc,
                causal=causal, sm_scale=sm_scale, block_q=bq, block_k=bk)
            o_c = o_c.astype(jnp.float32)
            lse_c = lse_flat.reshape(b, h, s_loc)
        else:
            kv_pos = src * s_loc + jnp.arange(s_loc)
            if causal:
                mask = q_pos[:, None] >= kv_pos[None, :]  # global causal
                mask = mask[None, None, :, :]             # [1,1,sq,sk]
            else:
                mask = None
            o_c, lse_c = _chunk_attention(q, k_cur, v_cur, mask, sm_scale)
        o, lse = _merge(o, lse, o_c, lse_c)
        if step != n - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
    return o.astype(q.dtype)


def ring_attention(q, k, v, mesh=None, causal: bool = True,
                   seq_axis: str = "seq",
                   batch_axes: Tuple[str, ...] = ("data", "fsdp"),
                   heads_axis: str = "tensor"):
    """jit-level ring attention: shard_maps over the mesh's sequence axis.

    q, k, v: [b, s, h, hd] global arrays (GQA pre-expanded).  Batch stays
    sharded over ``batch_axes``, heads over ``heads_axis``; the sequence
    axis rotates K/V chunks around the ring.
    """
    from jax.sharding import PartitionSpec as P

    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or mesh.empty:
            raise ValueError("ring_attention requires a mesh "
                             "(pass mesh= or trace under `with mesh:`)")
    axis_names = set(mesh.axis_names)
    batch = tuple(a for a in batch_axes if a in axis_names)
    heads = heads_axis if heads_axis in axis_names else None
    spec = P(batch if batch else None, seq_axis, heads, None)

    fn = functools.partial(
        ring_attention_sharded, axis_name=seq_axis, causal=causal)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)(q, k, v)
