"""Paged attention: single-token decode over a paged KV cache.

The reference delegates LLM serving to vLLM via compiled DAGs
(SURVEY.md §2.2 P12 — "Ray's µs-latency GPU pipeline path"); the
TPU-native build owns the inference path instead (§7.10 "LLM inference
replica w/ paged attention"). KV blocks live in fixed-size pages laid
out ROW-MAJOR with all KV heads fused into the row:

    k_pages / v_pages: [P, page, KVH * D]

so one page is ONE contiguous HBM region covering every kv head — the
decode kernel streams it with a single large DMA (64 KB at page=64,
KVH*D=512) instead of one 4 KB copy per (head, page) pair.  DMA size is
what decides decode bandwidth on TPU: the per-(head,page) scheme
measured 130-150 GB/s on v5e, the fused-row layout streams at several
hundred GB/s.  Each sequence owns a list of pages (its block table), so
cache memory is allocated page-at-a-time with zero fragmentation-driven
copies: the vLLM idea, TPU-shaped.

  - decode on TPU runs the in-tree Pallas GQA kernel below: grid
    (batch, context blocks), double-buffered manual DMAs of whole
    fused-head pages, flash-style online softmax across blocks, and
    length-based block skip so short contexts don't pay for the table
    width.
  - other platforms use an XLA gather formulation, and the same Pallas
    kernel runs in interpret mode for kernel-semantics tests on CPU.
  - prompt-page writes are functional scatters; decode-token writes go
    through an aliased sublane-strip RMW kernel (write_token_rows).

Static shapes throughout: [B, max_pages] block tables padded with page
0 and masked by context_lens, bucketed by the engine to the live
context width (serve/llm_engine.py), so a handful of compiled decode
programs serve every batch composition.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import dispatch


def paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                    sm_scale: float | None = None):
    """Decode-time attention for one new token per sequence.

    q:            [B, H, D]            query for the current position
    k_pages:      [P, page, KVH*D]     paged key cache (one layer)
    v_pages:      [P, page, KVH*D]     paged value cache
    block_tables: [B, max_pages] int32 page ids (padded entries ignored)
    context_lens: [B] int32            tokens in cache per sequence
                                       (including the current one)
    Returns [B, H, D].  KVH is inferred from the fused row width.
    """
    B, H, D = q.shape
    P, page, KD = k_pages.shape
    KVH = KD // D
    W = block_tables.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    on_tpu = dispatch.platform() == "tpu"
    # Kernel tiling constraints: fused row must fill whole lanes and a
    # page must cover the bf16 sublane tile.
    kernel_ok = (KD % 128 == 0 and H % KVH == 0 and page % 8 == 0)
    if (on_tpu or dispatch.interpret_mode()) and kernel_ok:
        dispatch.record("paged_attention",
                        "pallas" if on_tpu else "interpret")
        return _paged_attention_pallas(
            q, k_pages, v_pages, block_tables, context_lens, scale,
            interpret=not on_tpu)
    dispatch.record("paged_attention", "xla")
    return _paged_attention_gather(
        q, k_pages, v_pages, block_tables, context_lens, scale)


def _paged_attention_gather(q, k_pages, v_pages, block_tables,
                            context_lens, scale: float):
    """XLA gather formulation (non-TPU fallback)."""
    B, H, D = q.shape
    P, page, KD = k_pages.shape
    KVH = KD // D
    max_pages = block_tables.shape[1]
    G = H // KVH  # query heads per kv head (GQA)

    # Gather each sequence's pages: [B, max_pages, page, KVH*D] →
    # [B, KVH, T, D] with T = max_pages * page.
    k = jnp.take(k_pages, block_tables, axis=0).reshape(
        B, max_pages * page, KVH, D).transpose(0, 2, 1, 3)
    v = jnp.take(v_pages, block_tables, axis=0).reshape(
        B, max_pages * page, KVH, D).transpose(0, 2, 1, 3)

    qg = q.reshape(B, KVH, G, D)
    logits = jnp.einsum("bkgd,bktd->bkgt", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    t_idx = jnp.arange(max_pages * page, dtype=jnp.int32)
    valid = t_idx[None, :] < context_lens[:, None]           # [B, T]
    logits = jnp.where(valid[:, None, None, :], logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgt,bktd->bkgd", probs, v.astype(jnp.float32))
    return out.reshape(B, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# TPU decode kernel: grid (B/SB, blocks-of-pages).  Each grid step
# streams one compute block (ppcb fused-head pages) for each of SB
# sequences into VMEM with double-buffered async copies — one DMA per
# PAGE, each covering every kv head — and folds them into flash-style
# running (m, l, acc) scratch.  Batching SB sequences per step is what
# makes decode track the bandwidth roofline: with one sequence per step
# (r4) the kernel paid ~17 us of grid-step overhead per 0.5 MB of
# traffic (measured 4.0 ms/layer-iter at B=128 W=2 page=128 vs the
# 1.8 ms roofline); SB sequences amortize that overhead and keep
# SB*ppcb*2 DMAs in flight per step.  Blocks past every member
# sequence's context are skipped: no compute AND no copy, so cost
# tracks live context at SB granularity, not table width.
# ---------------------------------------------------------------------------


def _next_active(b, i, bctx_ref, blk: int, NB: int, NSB: int):
    """First grid position at or after (b, i) whose sequence-block
    holds live context for ANY member (bctx_ref: per-block max ctx).
    Blocks whose max ctx == 0 are skipped whole."""

    def cond(state):
        bb, ii = state
        done = bb >= NSB
        live = jnp.logical_and(
            bb < NSB,
            ii * blk < bctx_ref[jnp.minimum(bb, NSB - 1)])
        return jnp.logical_and(~done, ~live)

    def step(state):
        bb, ii = state
        # Block ii dead for seq-block bb: later blocks are dead too
        # (context is a prefix), so advance to the next seq-block.
        return bb + 1, jnp.zeros_like(ii)

    nb, ni = jax.lax.while_loop(cond, step, (b, i))
    return nb, ni


def _gqa_decode_kernel(tables_ref, ctx_ref, bctx_ref, q_ref, kf_ref,
                       vf_ref, o_ref, m_ref, l_ref, acc_ref, logit_ref,
                       k_buf, v_buf, buf_ref, sems, *, page: int,
                       ppcb: int, NB: int, B: int, SB: int, kvh: int,
                       g: int, d: int, scale: float):
    b = pl.program_id(0)           # sequence-block index (SB rows)
    i = pl.program_id(1)
    blk = page * ppcb
    NSB = B // SB
    bctx = bctx_ref[b]             # max ctx within this seq-block
    live = i * blk < bctx

    def copies(bb, ii, slot):
        """Async copies loading block (bb, ii) into buffer `slot` —
        recreated identically at start and wait time (each descriptor
        pairs one fused-head page with one buffer slice)."""
        out = []
        for s in range(SB):
            row = jnp.minimum(bb * SB + s, B - 1)
            for j in range(ppcb):
                pg = tables_ref[row, ii * ppcb + j]
                out.append(pltpu.make_async_copy(
                    kf_ref.at[pg], k_buf.at[slot, s, j],
                    sems.at[slot, 0]))
                out.append(pltpu.make_async_copy(
                    vf_ref.at[pg], v_buf.at[slot, s, j],
                    sems.at[slot, 1]))
        return out

    # The buffer parity is a running toggle over ACTIVE steps (SMEM
    # scratch), not i % 2: with skipped blocks and row transitions the
    # producing step's slot would otherwise disagree with the consuming
    # step's.
    fb, fi = _next_active(jnp.zeros_like(b), jnp.zeros_like(i),
                          bctx_ref, blk, NB, NSB)
    is_first = jnp.logical_and(b == fb, i == fi)

    @pl.when(jnp.logical_and(bctx == 0, i == NB - 1))
    def _zero_dead():
        # No block of an all-dead seq-block is live, so nothing below
        # would write its output — without this the (SB, H, D) VMEM
        # output block flushes back holding the PREVIOUS block's
        # attention.  Dead rows return defined zeros instead.
        o_ref[...] = jnp.zeros_like(o_ref[...])

    @pl.when(is_first)
    def _prime():
        # The very first active step has no predecessor to prefetch for
        # it: issue its own copies (they complete during grid ramp-up).
        buf_ref[0] = 0
        for c in copies(b, i, 0):
            c.start()

    @pl.when(live)
    def _step():
        slot = buf_ref[0]
        # Issue the NEXT active block's copies before touching this
        # block's data: the wait below then overlaps the next DMA wave.
        nb, ni = _next_active(
            jnp.where(i + 1 < NB, b, b + 1),
            jnp.where(i + 1 < NB, i + 1, 0),
            bctx_ref, blk, NB, NSB)

        @pl.when(nb < NSB)
        def _prefetch():
            for c in copies(nb, ni, 1 - slot):
                c.start()

        for c in copies(b, i, slot):
            c.wait()
        buf_ref[0] = 1 - slot

        @pl.when(i == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # Phase 1 — logits: per-(row, head) MXU dots into ONE stacked
        # [SB*H, blk] tile.  The dots are irreducibly per-head (GQA
        # attention is block-diagonal over kv heads), but stacking
        # their outputs lets phase 2 run ONE vectorized softmax-update
        # chain over full 8-sublane tiles instead of SB*KVH tiny [G,
        # blk] chains — the r4 kernel issued ~1k scalar-core ops per
        # call that way and ran 2x+ off the bandwidth roofline.
        for s in range(SB):
            kb = k_buf[slot, s].reshape(blk, kvh * d)
            q = q_ref[s]                                      # [H, D]
            for h in range(kvh):
                k_h = kb[:, h * d:(h + 1) * d]
                q_h = q[h * g:(h + 1) * g]                    # [G, D]
                logit_ref[s * kvh * g + h * g:
                          s * kvh * g + (h + 1) * g, :] = \
                    jax.lax.dot_general(
                        q_h, k_h, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)

        # Phase 2 — one flash update over the whole [SB*H, blk] tile.
        # ctx per stacked row: ctx_ref[b*SB + s] broadcast over H,
        # built with iota+select (dynamic_update_slice doesn't lower
        # in Mosaic).
        seq_of_row = jax.lax.broadcasted_iota(
            jnp.int32, (SB * kvh * g, 1), 0) // (kvh * g)
        ctx_col = jnp.zeros((SB * kvh * g, 1), jnp.int32)
        for s in range(SB):
            ctx_col = jnp.where(seq_of_row == s,
                                ctx_ref[b * SB + s], ctx_col)
        pos = i * blk + jax.lax.broadcasted_iota(
            jnp.int32, (SB * kvh * g, blk), 1)
        logits = logit_ref[...] * scale
        logits = jnp.where(pos < ctx_col, logits, -jnp.inf)
        m_prev = m_ref[...]                       # [SB*H, 1]
        m_new = jnp.maximum(m_prev,
                            jnp.max(logits, axis=-1, keepdims=True))
        # Rows past their context this block (or dead): m stays -inf;
        # exp(-inf - -inf) = exp(nan) guard via where.
        alpha = jnp.where(jnp.isneginf(m_prev) & jnp.isneginf(m_new),
                          0.0, jnp.exp(m_prev - m_new))
        p = jnp.exp(logits - m_new)               # [SB*H, blk]
        p = jnp.where(jnp.isneginf(m_new), 0.0, p)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1,
                                                  keepdims=True)
        m_ref[...] = m_new

        # Phase 3 — p·V per (row, head) dots off the stacked p tile.
        pb = p.astype(v_buf.dtype)
        for s in range(SB):
            vb = v_buf[slot, s].reshape(blk, kvh * d)
            for h in range(kvh):
                v_h = vb[:, h * d:(h + 1) * d]
                r0 = s * kvh * g + h * g
                pv = jax.lax.dot_general(
                    pb[r0:r0 + g, :], v_h, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)       # [G, D]
                acc_ref[r0:r0 + g, :] = \
                    acc_ref[r0:r0 + g, :] * alpha[r0:r0 + g] + pv

        # Finalize every row whose context ends in this block; zero
        # dead rows (ctx == 0) inside a live seq-block.
        @pl.when((i + 1) * blk >= bctx)
        def _finalize():
            l = jnp.maximum(l_ref[...], 1e-30)
            live_rows = ctx_col > 0
            out = jnp.where(live_rows, acc_ref[...] / l, 0.0)
            o_ref[...] = out.reshape(SB, kvh * g, d).astype(o_ref.dtype)


def _paged_attention_pallas(q, k_pages, v_pages, block_tables,
                            context_lens, scale: float, *,
                            interpret: bool):
    B, H, D = q.shape
    P, page, KD = k_pages.shape
    KVH = KD // D
    W = block_tables.shape[1]
    G = H // KVH
    # ~512-token compute blocks: big enough that the per-page DMAs
    # amortize grid-step latency, small enough that length-based skip
    # still saves traffic on short contexts.  W and page are pow-2 in
    # practice; fall back to 1-page blocks otherwise.
    ppcb = max(1, min(512 // page, W))
    while W % ppcb:
        ppcb -= 1
    NB = W // ppcb
    # Sequences per grid step: as many as keep the double-buffered
    # K/V blocks within ~8 MB of VMEM (half the core's budget, leaving
    # room for q/out/acc and the next block's buffers).
    blk_bytes = ppcb * page * KD * k_pages.dtype.itemsize * 4  # k+v, dbl
    SB = max(1, min(B, int(8e6 // max(blk_bytes, 1))))
    SB = 1 << (SB.bit_length() - 1)  # pow-2 for clean division
    if os.environ.get("RAY_TPU_PA_SB"):  # perf experiments only
        SB = max(1, min(B, int(os.environ["RAY_TPU_PA_SB"])))
    # Pad the batch up to a multiple of SB instead of shrinking SB to a
    # divisor (a prime B would degrade to SB=1, reinstating the per-row
    # grid overhead the batching exists to remove).  Padded rows carry
    # ctx 0: the skip logic never streams blocks for them beyond what
    # their seq-block's live rows need, and _finalize zeroes dead rows.
    B_in = B
    B = -(-B // SB) * SB
    if B != B_in:
        pad = B - B_in
        q = jnp.concatenate([q, jnp.zeros((pad, H, D), q.dtype)])
        block_tables = jnp.concatenate(
            [block_tables, jnp.zeros((pad, W), block_tables.dtype)])
        context_lens = jnp.concatenate(
            [jnp.asarray(context_lens, jnp.int32),
             jnp.zeros((pad,), jnp.int32)])

    # Per-seq-block max context for the skip logic.
    bctx = jnp.max(context_lens.astype(jnp.int32).reshape(B // SB, SB),
                   axis=1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B // SB, NB),
        in_specs=[
            pl.BlockSpec((SB, H, D),
                         lambda b, i, tables, ctx, bctx: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # k_pages (manual DMA)
            pl.BlockSpec(memory_space=pl.ANY),  # v_pages
        ],
        out_specs=pl.BlockSpec(
            (SB, H, D), lambda b, i, tables, ctx, bctx: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((SB * H, 1), jnp.float32),        # m
            pltpu.VMEM((SB * H, 1), jnp.float32),        # l
            pltpu.VMEM((SB * H, D), jnp.float32),        # acc
            pltpu.VMEM((SB * H, page * ppcb), jnp.float32),  # logits
            pltpu.VMEM((2, SB, ppcb, page, KD), k_pages.dtype),
            pltpu.VMEM((2, SB, ppcb, page, KD), v_pages.dtype),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = pl.pallas_call(
        functools.partial(_gqa_decode_kernel, page=page, ppcb=ppcb,
                          NB=NB, B=B, SB=SB, kvh=KVH, g=G, d=D,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret,
        name="paged_attention_decode",
    )
    out = kernel(block_tables.astype(jnp.int32),
                 context_lens.astype(jnp.int32), bctx, q, k_pages,
                 v_pages)
    return out[:B_in] if B != B_in else out


def write_page_tokens(k_pages, v_pages, k_new, v_new, block_tables,
                      positions):
    """Scatter new K/V rows into their pages (prefill path).

    k_pages/v_pages: [P, page, KVH*D] (fused-head rows);
    k_new/v_new: [B, S, KVH, D] projections for S new tokens per seq;
    positions:   [B, S] int32 absolute positions (define page + offset);
    block_tables:[B, max_pages].
    Returns updated (k_pages, v_pages). Rows with position < 0 are
    dropped (out-of-bounds page under scatter mode="drop") so padded
    prefills are safe.
    """
    B, S, KVH, D = k_new.shape
    page = k_pages.shape[1]
    page_idx = positions // page                              # [B, S]
    offset = positions % page
    valid = positions >= 0
    pages = jnp.take_along_axis(
        block_tables, jnp.maximum(page_idx, 0), axis=1)       # [B, S]
    # Invalid rows get page index == num_pages: past-the-end is
    # out-of-bounds under scatter mode="drop" (negative indices would
    # WRAP, silently corrupting the last page), so those writes vanish.
    pages = jnp.where(valid, pages, k_pages.shape[0])
    flat_pages = pages.reshape(-1)                            # [B*S]
    flat_off = jnp.maximum(offset, 0).reshape(-1)
    k_flat = k_new.reshape(-1, KVH * D)                       # [N, KD]
    v_flat = v_new.reshape(-1, KVH * D)
    k_pages = k_pages.at[flat_pages, flat_off].set(k_flat, mode="drop")
    v_pages = v_pages.at[flat_pages, flat_off].set(v_flat, mode="drop")
    return k_pages, v_pages


def _row_write_kernel(pages_ref, strips_ref, rows_ref, kf_ref, vf_ref,
                      knew_ref, vnew_ref, ok_ref, ov_ref, k_buf, v_buf,
                      sems, *, SB: int, strip: int, kd: int):
    """SB-batched read-modify-write: each grid step streams SB
    (page, strip) sublane strips in with manual DMAs, overwrites row
    rows[b] of each with the new token's fused-head K/V row, and
    streams them back.  One strip per grid step (the r4 shape) cost
    ~0.35 us of grid overhead per strip — 2,816 steps per decode
    iteration at B=128 x 22 layers ≈ 1 ms/iter; SB strips per step
    amortize it and keep 2*SB DMAs in flight each way.

    Aliased outputs (ok/ov are kf/vf) make the write genuinely in
    place.  Concurrent write-back order is NOT defined, which is safe
    because duplicate (page, strip) targets cannot carry different
    live data: each decode slot writes its own private generation
    page (shared prefix-cache pages are full, immutable prompt pages
    no decode position maps to), the clamped tail duplicates rewrite
    row B-1's identical strip, and dropped rows (position < 0) all
    land in the reserved never-read scratch page."""
    g = pl.program_id(0)

    def row_at(s):
        return g * SB + s  # SB divides the batch (wrapper guarantees)

    # Phase 1: pull all SB strips into VMEM.
    for s in range(SB):
        b = row_at(s)
        pltpu.make_async_copy(
            kf_ref.at[pages_ref[b], pl.ds(strips_ref[b] * strip, strip)],
            k_buf.at[s], sems.at[0]).start()
        pltpu.make_async_copy(
            vf_ref.at[pages_ref[b], pl.ds(strips_ref[b] * strip, strip)],
            v_buf.at[s], sems.at[1]).start()
    for s in range(SB):
        b = row_at(s)
        pltpu.make_async_copy(
            kf_ref.at[pages_ref[b], pl.ds(strips_ref[b] * strip, strip)],
            k_buf.at[s], sems.at[0]).wait()
        pltpu.make_async_copy(
            vf_ref.at[pages_ref[b], pl.ds(strips_ref[b] * strip, strip)],
            v_buf.at[s], sems.at[1]).wait()
    # Phase 2: overwrite each strip's target row.
    strip_pos = jax.lax.broadcasted_iota(jnp.int32, (strip, kd), 0)
    for s in range(SB):
        b = row_at(s)
        # knew/vnew arrive as this grid step's (SB, KD) block, so the
        # row index is STATIC (Mosaic cannot prove alignment of a
        # dynamic sublane load).
        k_buf[s] = jnp.where(strip_pos == rows_ref[b],
                             knew_ref[s], k_buf[s])
        v_buf[s] = jnp.where(strip_pos == rows_ref[b],
                             vnew_ref[s], v_buf[s])
    # Phase 3: write back (order undefined; see docstring for why
    # duplicate targets never carry different live data).
    for s in range(SB):
        b = row_at(s)
        pltpu.make_async_copy(
            k_buf.at[s],
            ok_ref.at[pages_ref[b], pl.ds(strips_ref[b] * strip, strip)],
            sems.at[0]).start()
        pltpu.make_async_copy(
            v_buf.at[s],
            ov_ref.at[pages_ref[b], pl.ds(strips_ref[b] * strip, strip)],
            sems.at[1]).start()
    for s in range(SB):
        b = row_at(s)
        pltpu.make_async_copy(
            k_buf.at[s],
            ok_ref.at[pages_ref[b], pl.ds(strips_ref[b] * strip, strip)],
            sems.at[0]).wait()
        pltpu.make_async_copy(
            v_buf.at[s],
            ov_ref.at[pages_ref[b], pl.ds(strips_ref[b] * strip, strip)],
            sems.at[1]).wait()


def write_token_rows(k_pages, v_pages, k_new, v_new, block_tables,
                     positions):
    """Decode-path single-token write: one fused [KVH*D] row per
    sequence, in place via an aliased Pallas kernel (NOT an XLA
    scatter).

    XLA's layout assignment gives a middle-axis scatter a different
    preferred cache layout (update rows contiguous) than the attention
    kernel's streaming layout, so a scatter here made every decode
    layer copy the multi-GB cache twice to ping-pong layouts — 238
    ms/iter on v5e.  A pallas_call pins the default layout on both
    sides and input_output_aliases makes the write genuinely in place.

    The RMW granule is one 8-row SUBLANE STRIP of the page, not the
    page itself: serving configs use big pages (64+ tokens — see the
    module docstring's DMA note), and carrying a whole page block
    through VMEM per written token would scale the write cost with
    page size.  The strip keeps per-token traffic constant regardless
    of page size.

    k_pages/v_pages: [FP, page, KVH*D]; k_new/v_new: [B, KVH, D];
    positions: [B] absolute position (< 0 = drop); block_tables:
    [B, W] (already layer-offset).  Dropped rows land in the GLOBAL
    scratch page FP-1 — the engine reserves the last physical page
    (llm_engine.py PageAllocator) so nothing lives there.
    """
    B, KVH, D = k_new.shape
    FP, page = k_pages.shape[0], k_pages.shape[1]
    KD = KVH * D
    strip = min(8, page)  # tiny test configs use page sizes < 8
    while page % strip:   # strip must tile the page dimension
        strip -= 1
    page_idx = positions // page
    offs = jnp.where(positions >= 0, positions % page, 0) \
        .astype(jnp.int32)
    pages = jnp.take_along_axis(
        block_tables, jnp.maximum(page_idx, 0)[:, None], axis=1)[:, 0]
    pages = jnp.where(positions >= 0, pages, FP - 1).astype(jnp.int32)
    strips = (offs // strip).astype(jnp.int32)
    rows = (offs % strip).astype(jnp.int32)

    if B == 0:  # empty batch traces to an empty grid
        return k_pages, v_pages
    SB = min(16, B)
    kn, vn = k_new.reshape(B, KD), v_new.reshape(B, KD)
    # Pad to a multiple of SB by duplicating the last row rather than
    # shrinking SB to a divisor (prime B would fall back to one strip
    # per grid step).  The duplicates rewrite row B-1's strip with
    # byte-identical data, which the kernel's duplicate-target
    # invariant (see _row_write_kernel) already covers.
    Bp = -(-B // SB) * SB
    if Bp != B:
        pad = Bp - B

        def _dup_tail(a):
            return jnp.concatenate(
                [a, jnp.broadcast_to(a[-1:], (pad, *a.shape[1:]))])

        pages, strips, rows = map(_dup_tail, (pages, strips, rows))
        kn, vn = _dup_tail(kn), _dup_tail(vn)
        B = Bp
    grid = (B // SB,)
    # There is no XLA formulation of this write (see above), so off TPU
    # the same kernel runs interpreted.
    on_tpu = dispatch.platform() == "tpu"
    dispatch.record("write_token_rows", "pallas" if on_tpu else "interpret")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # k_pages (manual DMA)
            pl.BlockSpec(memory_space=pl.ANY),  # v_pages
            pl.BlockSpec((SB, KD),
                         lambda g, pages, strips, rows: (g, 0)),
            pl.BlockSpec((SB, KD),
                         lambda g, pages, strips, rows: (g, 0)),
        ],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((SB, strip, KD), k_pages.dtype),
            pltpu.VMEM((SB, strip, KD), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = pl.pallas_call(
        functools.partial(_row_write_kernel, SB=SB, strip=strip,
                          kd=KD),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
        # Indices count every positional operand including the three
        # scalar-prefetch arrays: 3 = k_pages -> out 0, 4 = v_pages.
        input_output_aliases={3: 0, 4: 1},
        interpret=not on_tpu,
        name="write_token_rows",
    )
    return kernel(pages, strips, rows, k_pages, v_pages, kn, vn)


def paged_attention_reference(q, k_pages, v_pages, block_tables,
                              context_lens):
    """O(B·T) numpy-style reference for tests: per-sequence dense
    attention over the gathered cache."""
    import numpy as np

    q = np.asarray(q, dtype=np.float64)
    k_pages = np.asarray(k_pages, dtype=np.float64)
    v_pages = np.asarray(v_pages, dtype=np.float64)
    block_tables = np.asarray(block_tables)
    context_lens = np.asarray(context_lens)
    B, H, D = q.shape
    P, page, KD = k_pages.shape
    KVH = KD // D
    G = H // KVH
    out = np.zeros_like(q)
    for b in range(B):
        n = int(context_lens[b])
        if n == 0:
            continue
        ks, vs = [], []
        for t in range(n):
            p = block_tables[b, t // page]
            ks.append(k_pages[p, t % page].reshape(KVH, D))
            vs.append(v_pages[p, t % page].reshape(KVH, D))
        k = np.stack(ks)  # [n, KVH, D]
        v = np.stack(vs)
        for h in range(H):
            kh = h // G
            logits = (k[:, kh] @ q[b, h]) / np.sqrt(D)
            w = np.exp(logits - logits.max())
            w = w / w.sum()
            out[b, h] = w @ v[:, kh]
    return out
