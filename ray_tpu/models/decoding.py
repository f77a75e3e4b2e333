"""Autoregressive decoding over a paged KV cache.

The reference serves LLMs by delegating to vLLM over compiled DAGs
(SURVEY.md P12); here the inference path is owned end to end: prefill
writes the prompt's K/V into pages, decode_step advances every active
sequence one token with paged attention (ops/paged_attention.py). Both
are single jitted programs with static shapes — [max_batch] slots,
[B, max_pages] block tables — so continuous batching (serve/llm_engine.py)
never recompiles as requests come and go.

Numerics intentionally mirror models/transformer.py `forward` (same
rms_norm/rope/projection order), so greedy decode reproduces the full
forward's argmax token-for-token — tested in tests/test_llm_decoding.py.
MoE blocks decode too: prefill routes through the same capacity-based
moe_ffn as training, decode steps use the exact gather path
(moe.moe_ffn_gather) so no live sequence's token is capacity-dropped.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.transformer import (
    TransformerConfig,
    apply_rope,
    rms_norm,
    rope_freqs,
)
from ray_tpu.ops import dispatch
from ray_tpu.ops.attention import flash_attention
from ray_tpu.util import device_stats
from ray_tpu.ops.paged_attention import (
    paged_attention,
    write_page_tokens,
    write_token_rows,
)


def _use_flash_prefill(seq: int, head_dim: int) -> bool:
    """Prefill attention runs the Pallas flash kernel when the segment
    shape allows it.  The dense einsum path materializes [B, H, S, S]
    scores + probs in HBM (~1.3 GB f32 per layer at the serving bench's
    B=128 S=128 — measured 0.24 MFU prefill); flash never does.

    Correctness with padding: prefill positions are always a contiguous
    arange(L) prefix followed by -1 pads, so causal masking BY ROW
    INDEX already hides every pad key from every valid query (a valid
    query at index p sees only indices <= p, all valid); pad queries'
    outputs are never read (last-valid-position selection).  The same
    argument covers fully-pad bucket rows, which only attend
    themselves."""
    if not (dispatch.platform() == "tpu" or dispatch.interpret_mode()):
        return False
    # At short segments (<= 128) the dense per-segment scores are small
    # and XLA's fused einsum path measures slightly faster than the
    # kernel's grid overhead; flash wins from 256 up (and is the only
    # viable path at 1k+, where dense scores would be GBs).
    if seq < 256:
        return False
    block = min(512, seq)
    return seq % block == 0 and head_dim % 64 == 0


def _prefill_attention(q, k, v, mask, c: TransformerConfig):
    """Segment-local attention for prefill bodies: flash kernel when
    possible, dense masked softmax otherwise.  q/k/v: [B, S, H|KVH, D]
    (GQA repeat happens here); mask: [B, 1, S, S] bool for the dense
    path."""
    B, S = q.shape[:2]
    if q.shape[2] != k.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if _use_flash_prefill(S, c.head_dim_):
        blk = min(512, S)
        return flash_attention(q, k, v, causal=True,
                               block_q=blk, block_k=blk)
    # Dense by choice (short segment) or by platform; flash_attention
    # records its own path when it is taken.
    dispatch.record("prefill_attention_dense", "xla")
    scale = 1.0 / math.sqrt(c.head_dim_)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32),
                           axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def init_kv_pages(config: TransformerConfig, num_pages: int,
                  page_size: int) -> Dict[str, jax.Array]:
    """Paged KV cache for all layers, fused-head rows:
    [L, P, page, KVH * head_dim] — one page is one CONTIGUOUS HBM
    region covering every kv head, so the decode kernel streams it as
    a single large DMA (ops/paged_attention.py module docstring).  L
    and P are adjacent so the flat [L*P, page, KD] view is a free
    reshape and layer l's page p addresses as flat page l*P + p."""
    c = config
    shape = (c.num_layers, num_pages, page_size,
             c.num_kv_heads * c.head_dim_)
    return {"k": jnp.zeros(shape, dtype=c.dtype),
            "v": jnp.zeros(shape, dtype=c.dtype)}


def _layer_params(params: Dict[str, Any], l: int):
    """Blocks are stacked [L, ...] (scan layout); slice out layer l."""
    return jax.tree.map(lambda x: x[l], params["blocks"])


def _flat_cache(cache: Dict[str, jax.Array]):
    """View the [L, P, page, KD] cache as [L*P, page, KD].

    Layer l's page p lives at flat index l*P + p, so per-layer writes
    are ONE scatter into the whole cache instead of slice-out /
    scatter / write-back — the latter pattern defeated XLA's in-place
    analysis and copied ~2 x 33 MB of pages per layer per decode step
    (the dominant cost of the r2 decode bench).  Reshape of a
    contiguous array is metadata-only; the engine-facing cache dict
    keeps its [L, ...] shape."""
    L, P = cache["k"].shape[:2]
    rest = cache["k"].shape[2:]
    return (cache["k"].reshape(L * P, *rest),
            cache["v"].reshape(L * P, *rest), L, P)


def _unflat_cache(kf, vf, L: int, P: int) -> Dict[str, jax.Array]:
    rest = kf.shape[1:]
    return {"k": kf.reshape(L, P, *rest),
            "v": vf.reshape(L, P, *rest)}


def _project_qkv(x, bp, positions, cos, sin, c: TransformerConfig):
    """Shared prefill/decode Q/K/V computation ([B, S, ...])."""
    b, s, h = x.shape
    hd = c.head_dim_
    y = rms_norm(x, bp["attn_norm"], c.rms_eps)
    q = (y @ bp["wq"].astype(c.dtype)).reshape(b, s, c.num_heads, hd)
    k = (y @ bp["wk"].astype(c.dtype)).reshape(b, s, c.num_kv_heads, hd)
    v = (y @ bp["wv"].astype(c.dtype)).reshape(b, s, c.num_kv_heads, hd)
    safe_pos = jnp.maximum(positions, 0)
    q = apply_rope(q, cos, sin, safe_pos)
    k = apply_rope(k, cos, sin, safe_pos)
    return q, k, v


def _mlp(x, bp, c: TransformerConfig, positions=None):
    y = rms_norm(x, bp["mlp_norm"], c.rms_eps)
    if c.num_experts > 0:
        from ray_tpu.models.moe import moe_ffn, moe_ffn_gather

        B, S, h = x.shape
        y2d = y.reshape(B * S, h)
        if S == 1:
            # Decode step: exact gather path — a capacity cutoff over
            # T = B tokens could silently drop a live sequence's token.
            out2d = moe_ffn_gather(
                y2d, bp["router"], bp["we_gate"], bp["we_up"],
                bp["we_down"],
                num_experts_per_token=c.num_experts_per_token,
                dtype=c.dtype)
        else:
            # Prefill: same capacity-based program as the training
            # forward, with pad-bucket tokens (positions < 0) masked
            # out of routing so they never crowd real tokens out of
            # expert capacity.
            valid = (positions.reshape(-1) >= 0) \
                if positions is not None else None
            out2d, _ = moe_ffn(
                y2d, bp["router"], bp["we_gate"], bp["we_up"],
                bp["we_down"],
                num_experts_per_token=c.num_experts_per_token,
                capacity_factor=c.capacity_factor, dtype=c.dtype,
                valid=valid)
        return x + out2d.reshape(B, S, h)
    gate = jax.nn.silu(y @ bp["w_gate"].astype(c.dtype))
    up = y @ bp["w_up"].astype(c.dtype)
    return x + ((gate * up) @ bp["w_down"].astype(c.dtype))


def _lm_head(x, params, c: TransformerConfig):
    x = rms_norm(x, params["final_norm"], c.rms_eps)
    # Read the embedding in its stored dtype and accumulate in fp32 on
    # the MXU (preferred_element_type) rather than materializing an
    # fp32 copy of the [vocab, h] table every decode iteration — the
    # numerics are identical (bf16 inputs are exact in fp32; products
    # and accumulation happen in fp32 either way) but the HBM read
    # halves.
    return jnp.einsum("bh,vh->bv", x.astype(c.dtype),
                      params["tok_embed"].astype(c.dtype),
                      preferred_element_type=jnp.float32)


@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def prefill(params, tokens, positions, cache, block_tables,
            config: TransformerConfig
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Process a (padded) prompt, writing its K/V into pages.

    tokens: [B, S] int32 (pad with anything); positions: [B, S] int32
    absolute positions, -1 on padding (pad K/V writes are dropped and
    pad queries masked). Returns (logits at each row's LAST valid
    position [B, vocab] fp32, updated cache).
    """
    c = config
    assert c.scan_layers, \
        "decoding expects stacked [L, ...] block params (scan_layers=True)"
    B, S = tokens.shape
    x = params["tok_embed"].astype(c.dtype)[tokens]
    cos, sin = rope_freqs(c.head_dim_, c.max_seq_len, c.rope_theta)
    # Causal within the prompt, restricted to valid (non-pad) keys.
    q_pos = positions[:, :, None]                  # [B, S, 1]
    k_pos = positions[:, None, :]                  # [B, 1, S]
    mask = (k_pos >= 0) & (q_pos >= 0) & (k_pos <= q_pos)  # [B, S, S]
    mask = mask[:, None, :, :]                     # [B, 1, S, S]

    ck, cv, L, P = _flat_cache(cache)
    for l in range(c.num_layers):
        bp = _layer_params(params, l)
        q, k, v = _project_qkv(x, bp, positions, cos, sin, c)
        ck, cv = write_page_tokens(ck, cv, k, v,
                                   block_tables + l * P, positions)
        attn = _prefill_attention(q, k, v, mask, c)
        x = x + attn.reshape(B, S, -1) @ bp["wo"].astype(c.dtype)
        x = _mlp(x, bp, c, positions)

    # Last valid row per sequence.
    last = jnp.argmax(positions, axis=1)           # [B]
    x_last = jnp.take_along_axis(
        x, last[:, None, None], axis=1)[:, 0]      # [B, h]
    return _lm_head(x_last, params, c), _unflat_cache(ck, cv, L, P)


def _chunk_forward(params, tokens, positions, cache, block_tables,
                   c: TransformerConfig):
    """Shared body of chunked prefill / speculative verification:
    process a token chunk whose PRIOR context already lives in this
    sequence's pages, writing the chunk's K/V and attending to the
    full context via a page gather. Returns (x [B, S, h], cache)."""
    assert c.scan_layers, \
        "decoding expects stacked [L, ...] block params (scan_layers=True)"
    B, S = tokens.shape
    x = params["tok_embed"].astype(c.dtype)[tokens]
    cos, sin = rope_freqs(c.head_dim_, c.max_seq_len, c.rope_theta)
    page = cache["k"].shape[2]
    max_ctx = block_tables.shape[1] * page
    q_pos = positions[:, :, None]                   # [B, S, 1]
    k_pos = jnp.arange(max_ctx)[None, None, :]      # [1, 1, ctx]
    # Pages are assigned contiguously, so slot index IS absolute
    # position. Slots past the written region carry k_pos > max(q_pos)
    # (or a stale tenant's data beyond this row's table) and are masked.
    mask = (q_pos >= 0) & (k_pos <= q_pos)          # [B, S, ctx]
    mask = mask[:, None, :, :]                      # [B, 1, S, ctx]
    scale = 1.0 / math.sqrt(c.head_dim_)

    ck, cv, L, P = _flat_cache(cache)
    for l in range(c.num_layers):
        bp = _layer_params(params, l)
        q, k, v = _project_qkv(x, bp, positions, cos, sin, c)
        tables_l = block_tables + l * P
        ck, cv = write_page_tokens(ck, cv, k, v, tables_l, positions)
        # Gather the full context (cached prefix + just-written suffix)
        # from the pages; K in pages is already rotary-encoded.
        # [B, W, page, KVH*D] -> [B, ctx, KVH, D] (fused-head rows
        # split back into heads — a free trailing-dim reshape).
        kvh = c.num_kv_heads
        kf = ck[tables_l].reshape(B, max_ctx, kvh, c.head_dim_)
        vf = cv[tables_l].reshape(B, max_ctx, kvh, c.head_dim_)
        kv = kf.shape[2]
        if kv != c.num_heads:
            rep = c.num_heads // kv
            kf = jnp.repeat(kf, rep, axis=2)
            vf = jnp.repeat(vf, rep, axis=2)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kf) * scale
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(logits.astype(jnp.float32),
                               axis=-1).astype(x.dtype)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, vf)
        x = x + attn.reshape(B, S, -1) @ bp["wo"].astype(c.dtype)
        x = _mlp(x, bp, c, positions)
    return x, _unflat_cache(ck, cv, L, P)


@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def prefill_with_context(params, tokens, positions, cache, block_tables,
                         config: TransformerConfig
                         ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Chunked prefill: process a prompt SUFFIX whose earlier tokens'
    K/V already live in this sequence's pages (prefix caching,
    serve/llm_engine.py PrefixCache — the capability vLLM calls
    automatic prefix caching).

    tokens: [B, S] the suffix (padded); positions: [B, S] absolute
    positions starting at the first uncached token, -1 on padding.
    Attention keys are gathered from the pages AFTER the suffix K/V is
    written, so each query sees the cached prefix plus the causal
    in-window context through one mask on absolute positions. Returns
    (logits at each row's LAST valid position [B, vocab] fp32, cache).
    """
    x, cache = _chunk_forward(params, tokens, positions, cache,
                              block_tables, config)
    last = jnp.argmax(positions, axis=1)
    x_last = jnp.take_along_axis(
        x, last[:, None, None], axis=1)[:, 0]
    return _lm_head(x_last, params, config), cache


@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def verify_step(params, tokens, positions, cache, block_tables,
                config: TransformerConfig
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Speculative verification: process [last_token, draft...] as one
    chunk and return logits at EVERY position ([B, S, vocab] fp32) —
    position i's argmax is the model's token after consuming
    tokens[:i+1], which the engine compares against the draft
    (serve/llm_engine.py speculative decoding; the greedy
    prompt-lookup counterpart of vLLM's spec-decode path)."""
    x, cache = _chunk_forward(params, tokens, positions, cache,
                              block_tables, config)
    B, S, h = x.shape
    logits = _lm_head(x.reshape(B * S, h), params, config)
    return logits.reshape(B, S, -1), cache


def _decode_one(params, tokens, cache, block_tables, positions,
                context_lens, config: TransformerConfig
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step's body (unjitted; shared by decode_step and
    decode_multi_step)."""
    c = config
    assert c.scan_layers, \
        "decoding expects stacked [L, ...] block params (scan_layers=True)"
    B = tokens.shape[0]
    x = params["tok_embed"].astype(c.dtype)[tokens][:, None, :]  # [B,1,h]
    cos, sin = rope_freqs(c.head_dim_, c.max_seq_len, c.rope_theta)
    pos2d = positions[:, None]

    ck, cv, L, P = _flat_cache(cache)
    for l in range(c.num_layers):
        bp = _layer_params(params, l)
        q, k, v = _project_qkv(x, bp, pos2d, cos, sin, c)
        tables_l = block_tables + l * P
        # DUS row writes, not scatter: scatter's preferred layout
        # differs from the attention kernel's and XLA would copy the
        # whole cache per layer to convert (write_token_rows docstring).
        ck, cv = write_token_rows(ck, cv, k[:, 0], v[:, 0], tables_l,
                                  positions)
        attn = paged_attention(q[:, 0], ck, cv, tables_l, context_lens)
        x = x + (attn.reshape(B, 1, -1) @ bp["wo"].astype(c.dtype))
        x = _mlp(x, bp, c)

    return _lm_head(x[:, 0], params, c), _unflat_cache(ck, cv, L, P)


@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def decode_step(params, tokens, cache, block_tables, positions,
                context_lens, config: TransformerConfig
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Advance every slot one token.

    tokens: [B] int32 (the previously emitted token per slot);
    positions: [B] its absolute position; context_lens: [B] cache length
    INCLUDING this token. Returns (logits [B, vocab] fp32, cache).
    """
    return _decode_one(params, tokens, cache, block_tables, positions,
                       context_lens, config)


@partial(jax.jit, static_argnames=("config", "n_steps"),
         donate_argnames=("cache",))
def decode_multi_step(params, tokens, cache, block_tables, positions,
                      context_lens, limits, eos, config: TransformerConfig,
                      n_steps: int):
    """Advance every slot up to n_steps GREEDY tokens entirely on device
    (vLLM's multi-step scheduling, TPU-shaped): the argmax token feeds
    the next step without a host round trip, so the host syncs once per
    n_steps instead of per token — the difference between dispatch-bound
    and compute-bound decode on high-latency transports.

    limits: [B] int32 — highest absolute position a slot may WRITE
    (len(prompt)+max_new-1); a slot stops when its next write would
    exceed it.  eos: [B] int32 — per-slot EOS token id, -1 for none; a
    slot stops after emitting it.

    Returns (out [B, n_steps] int32 tokens, -1 past a slot's stop;
    tokens [B]; positions [B]; context_lens [B]; cache) — the final
    per-slot state comes back as DEVICE arrays so the engine can chain
    the next chunk off them without a host round trip: chunks dispatch
    back-to-back (pipelined behind the out transfer) and the device
    never idles on the host round trip (serve/llm_engine.py
    pipelined decode).
    """
    B = tokens.shape[0]

    def body(i, carry):
        tokens, cache, positions, ctx, out = carry
        alive = positions >= 0
        logits, cache = _decode_one(params, tokens, cache, block_tables,
                                    positions, ctx, config)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(alive, nxt, -1)
        out = out.at[:, i].set(nxt)
        hit_eos = alive & (eos >= 0) & (nxt == eos)
        new_pos = positions + 1
        stop = hit_eos | (new_pos > limits)
        positions = jnp.where(alive & ~stop, new_pos, -1)
        ctx = jnp.where(alive & ~stop, ctx + 1, ctx)
        tokens = jnp.where(alive, nxt, tokens)
        return tokens, cache, positions, ctx, out

    out0 = jnp.full((B, n_steps), -1, jnp.int32)
    tokens, cache, positions, ctx, out = jax.lax.fori_loop(
        0, n_steps, body,
        (tokens, cache, positions, context_lens, out0))
    return out, tokens, positions, ctx, cache


@partial(jax.jit, static_argnames=("config", "seg_len"),
         donate_argnames=("cache", "st_tokens", "st_positions", "st_ctx",
                          "st_limits", "st_eos"))
def packed_prefill_admit(params, tokens, positions, row_tables,
                         seg_slot, seg_limit, seg_eos, cache,
                         st_tokens, st_positions, st_ctx, st_limits,
                         st_eos, config: TransformerConfig,
                         seg_len: int):
    """Packed async prefill: process MANY equal-bucket prompt segments
    in one program, write their K/V pages, compute each segment's first
    greedy token, and fold the new slots into the device-chained decode
    state — zero host round trips (the engine reads the first tokens
    back later, off the critical path).

    Two layouts share one buffer (free reshapes of the same tokens):

      - matmuls/MLP run on [R, S] rows packing S/seg_len segments each
        — measured ~2x the MFU of the [nseg, seg_len] layout at
        short-prompt serving shapes (128-token prompts, v5e);
      - attention runs on the [R*S/seg_len, seg_len] per-segment view,
        so scores stay [nseg, H, seg_len, seg_len] instead of the
        packed row's quadratic [R, H, S, S].

    Segments are page-aligned within their row (seg_len % page_size
    == 0, positions start at 0), so a segment's token at row-local
    index j lands at page row_tables[r, j // page] offset j % page —
    identical to its absolute-position slot.

    tokens/positions: [R, S] (-1 positions = pad: K/V writes dropped,
    queries masked); row_tables: [R, S // page]; seg_slot/limit/eos:
    [NSEG = R*S/seg_len] per-segment decode-slot metadata (slot ==
    max_batch → unused segment, all its state scatters drop).

    Returns (first_tokens [NSEG] int32, cache, st_tokens, st_positions,
    st_ctx, st_limits, st_eos); st_* follow merge_slot_state semantics
    (st_positions = next write position, -1 when the request is already
    finished by its first token — max_new == 1 or instant EOS)."""
    c = config
    assert c.scan_layers, \
        "decoding expects stacked [L, ...] block params (scan_layers=True)"
    R, S = tokens.shape
    nseg = (R * S) // seg_len
    x = params["tok_embed"].astype(c.dtype)[tokens]
    cos, sin = rope_freqs(c.head_dim_, c.max_seq_len, c.rope_theta)
    page = cache["k"].shape[2]
    # Row-local positions drive paging; true positions drive RoPE and
    # the causal mask.  Alignment makes the two agree mod page.
    # Per-segment causal mask on the [nseg, seg_len] view (dense
    # fallback only — the flash path masks causally by row index,
    # which is equivalent for arange-prefix positions).
    pos_seg = positions.reshape(nseg, seg_len)
    q_pos = pos_seg[:, :, None]
    k_pos = pos_seg[:, None, :]
    mask = (k_pos >= 0) & (q_pos >= 0) & (k_pos <= q_pos)
    mask = mask[:, None, :, :]                     # [nseg, 1, sl, sl]

    ck, cv, L, P = _flat_cache(cache)
    for layer in range(c.num_layers):
        bp = _layer_params(params, layer)
        q, k, v = _project_qkv(x, bp, positions, cos, sin, c)
        # Write via ROW-LOCAL positions: page row_tables[r, j//page],
        # offset j%page; pad rows (true position < 0) still drop.
        rpos = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None], (R, S))
        rpos = jnp.where(positions >= 0, rpos, -1)
        ck, cv = write_page_tokens(ck, cv, k, v, row_tables + layer * P,
                                   rpos)
        # Attention on the per-segment view.
        hd = c.head_dim_
        qs = q.reshape(nseg, seg_len, c.num_heads, hd)
        ks = k.reshape(nseg, seg_len, c.num_kv_heads, hd)
        vs = v.reshape(nseg, seg_len, c.num_kv_heads, hd)
        attn = _prefill_attention(qs, ks, vs, mask, c)
        x = x + attn.reshape(R, S, -1) @ bp["wo"].astype(c.dtype)
        x = _mlp(x, bp, c, positions)

    # Per-segment last valid token -> lm head -> greedy first token.
    xs = x.reshape(nseg, seg_len, -1)
    last = jnp.argmax(pos_seg, axis=1)             # [nseg]
    x_last = jnp.take_along_axis(
        xs, last[:, None, None], axis=1)[:, 0]     # [nseg, h]
    logits = _lm_head(x_last, params, c)
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [nseg]
    ctx_len = jnp.sum(pos_seg >= 0, axis=1).astype(jnp.int32)  # = L

    # Fold into the decode state.  Unused segments carry slot ==
    # max_batch: past-the-end drops under mode="drop" (negative would
    # wrap — see write_page_tokens).
    # ctx_len == seg_limit means the first token was the last allowed
    # write-1 position's token (max_new_tokens == 1): already finished.
    finished = ((seg_eos >= 0) & (first == seg_eos)) \
        | (ctx_len >= seg_limit)
    new_pos = jnp.where(finished, -1, ctx_len)
    st_tokens = st_tokens.at[seg_slot].set(first, mode="drop")
    st_positions = st_positions.at[seg_slot].set(new_pos, mode="drop")
    st_ctx = st_ctx.at[seg_slot].set(ctx_len + 1, mode="drop")
    st_limits = st_limits.at[seg_slot].set(seg_limit, mode="drop")
    st_eos = st_eos.at[seg_slot].set(seg_eos, mode="drop")
    return (first, _unflat_cache(ck, cv, L, P), st_tokens, st_positions,
            st_ctx, st_limits, st_eos)


@partial(jax.jit, donate_argnames=("tokens", "positions", "context_lens",
                                   "limits", "eos"))
def merge_slot_state(tokens, positions, context_lens, limits, eos,
                     mask, new_tokens, new_positions, new_context_lens,
                     new_limits, new_eos):
    """Fold host-side slot changes (admissions, frees) into the
    device-chained decode state without reading it back: a masked
    select per array.  Used by the engine's pipelined decode path to
    admit requests between in-flight chunks."""
    sel = lambda n, o: jnp.where(mask, n, o)  # noqa: E731
    return (sel(new_tokens, tokens), sel(new_positions, positions),
            sel(new_context_lens, context_lens), sel(new_limits, limits),
            sel(new_eos, eos))


@jax.jit
def gather_kv_pages(cache, page_ids
                    ) -> Tuple[jax.Array, jax.Array]:
    """Read one request's KV pages out of the paged cache for handoff
    (serve disaggregation: the prefill replica exports these and the
    decode replica splices them in with splice_kv_pages).

    page_ids: [N] int32 physical page indices, pow-2 padded by the
    caller (pad rows gather an arbitrary live page; the caller slices
    them off host-side).  Returns (k, v) each [L, N, page, KD] — the
    all-layer column of those pages, one contiguous gather per array.
    """
    return cache["k"][:, page_ids], cache["v"][:, page_ids]


@partial(jax.jit, donate_argnames=("cache",))
def splice_kv_pages(cache, k_pages, v_pages, page_ids
                    ) -> Dict[str, jax.Array]:
    """Write imported KV pages into the paged cache (the decode side of
    the prefill→decode handoff): ONE scatter into the flat [L*P, ...]
    view per array, the same in-place layout the decode step's
    write_token_rows uses, so XLA updates the donated cache without
    copying it.

    k_pages/v_pages: [L, N, page, KD]; page_ids: [N] int32 physical
    destination pages, -1 for pad rows.  Pad rows route to flat index
    L*P — one past the end, dropped by the scatter — NOT to a per-layer
    sentinel, which would alias the next layer's page 0.
    """
    kf, vf, L, P = _flat_cache(cache)
    valid = page_ids >= 0
    idx = jnp.where(valid[None, :],
                    jnp.arange(L)[:, None] * P + page_ids[None, :],
                    L * P).reshape(-1)
    rest = k_pages.shape[2:]
    kf = kf.at[idx].set(k_pages.reshape(-1, *rest), mode="drop")
    vf = vf.at[idx].set(v_pages.reshape(-1, *rest), mode="drop")
    return _unflat_cache(kf, vf, L, P)


# Device-plane observability: every jit entry point is wrapped so each
# compilation after warmup is counted per function with shapes + wall
# time (the recompile-storm watchdog reads these via the profile
# sampler).  The wrapper forwards attribute access (.lower, AOT APIs)
# and costs one tracing-cache-size probe per call.
prefill = device_stats.count_compiles(prefill, "decoding.prefill")
prefill_with_context = device_stats.count_compiles(
    prefill_with_context, "decoding.prefill_with_context")
verify_step = device_stats.count_compiles(
    verify_step, "decoding.verify_step")
decode_step = device_stats.count_compiles(
    decode_step, "decoding.decode_step")
decode_multi_step = device_stats.count_compiles(
    decode_multi_step, "decoding.decode_multi_step")
packed_prefill_admit = device_stats.count_compiles(
    packed_prefill_admit, "decoding.packed_prefill_admit")
merge_slot_state = device_stats.count_compiles(
    merge_slot_state, "decoding.merge_slot_state")
gather_kv_pages = device_stats.count_compiles(
    gather_kv_pages, "decoding.gather_kv_pages")
splice_kv_pages = device_stats.count_compiles(
    splice_kv_pages, "decoding.splice_kv_pages")
