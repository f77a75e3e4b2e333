"""Mixture-of-experts FFN: the GSPMD capacity layer (`moe_ffn`, the dense
block's and decoding's) and the dropless layer that holds one chip's share of
the experts (`sigmoid_route`, `softmax_route` and the selection they share
with cca_moe.py's router, `select_experts`; `routed_experts`: the four
expert model files').

Greenfield capability (SURVEY.md §2.4 — expert parallelism is absent from
the reference; the TPU-native target is an expert mesh axis + all_to_all).
GShard/Switch-style dense dispatch: top-k routing with capacity, dispatch/
combine einsums, expert weights sharded on the "expert" logical axis —
XLA lowers the dispatch einsums to all_to_all over the expert mesh axis,
riding ICI (no hand-written collective needed; annotate and let GSPMD
place it).

Aux load-balancing loss per Switch Transformers (Fedus et al.):
  aux = E * Σ_e (fraction_tokens_e · mean_router_prob_e)
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import common
from ray_tpu.ops import dispatch, row_gather
from ray_tpu.parallel.sharding import with_logical_constraint


def moe_ffn(x, router_w, w_gate, w_up, w_down, *,
            num_experts_per_token: int = 2,
            capacity_factor: float = 1.25,
            dtype=jnp.bfloat16, valid=None) -> Tuple[jax.Array, jax.Array]:
    """MoE feed-forward on flattened tokens.

    x: [T, h]; router_w: [h, E]; w_gate/w_up: [E, h, m]; w_down: [E, m, h].
    valid: optional [T] bool — False rows (pad-bucket tokens in serving
    prefill) neither claim expert capacity nor produce output, so
    padding can't crowd real tokens out of their experts.
    Returns (out [T, h], aux_loss scalar fp32).
    """
    T, h = x.shape
    E = router_w.shape[-1]
    k = num_experts_per_token
    capacity = max(1, int(math.ceil(k * T / E * capacity_factor)))

    # -- routing (fp32 for numerics) ----------------------------------------
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))  # [T,E]
    probs = jax.nn.softmax(logits, axis=-1)

    gate_vals, expert_idx = jax.lax.top_k(probs, k)          # [T,k]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # -- aux load-balance loss (computed on ALL tokens, pre-capacity) -------
    assign1 = jax.nn.one_hot(expert_idx[:, 0], E)            # top-1 fraction
    frac_tokens = jnp.mean(assign1, axis=0)
    mean_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * mean_probs)

    # -- capacity assignment ------------------------------------------------
    # Position of each (token, slot) within its expert's buffer: running
    # count of prior assignments to the same expert across the flattened
    # [k, T] priority order (slot 0 of every token beats slot 1).
    flat_expert = expert_idx.T.reshape(-1)                   # [k*T]
    onehot = jax.nn.one_hot(flat_expert, E, dtype=jnp.int32)  # [kT,E]
    if valid is not None:
        # Invalid (pad) tokens are excluded BEFORE the running count so
        # they can't consume buffer slots ahead of real tokens.
        onehot = onehot * jnp.tile(
            valid.astype(jnp.int32), (k,))[:, None]
    pos_in_expert = jnp.cumsum(onehot, axis=0) - onehot      # [kT,E]
    pos = jnp.sum(pos_in_expert * onehot, axis=-1)           # [kT]
    keep = pos < capacity
    pos = jnp.where(keep, pos, 0)

    # back to [T,k]
    keep = keep.reshape(k, T).T
    pos = pos.reshape(k, T).T
    if valid is not None:
        keep = keep & valid[:, None]
    gate_vals = gate_vals * keep.astype(gate_vals.dtype)

    # dispatch [T,E,C] / combine [T,E,C]
    e_onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)   # [T,k,E]
    c_onehot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)   # [T,k,C]
    dispatch = jnp.einsum(
        "tke,tkc->tec", e_onehot * keep[..., None], c_onehot)
    combine = jnp.einsum(
        "tke,tkc->tec", e_onehot * gate_vals[..., None], c_onehot)

    # -- expert compute (all_to_all inserted by GSPMD on the expert axis) ---
    xin = jnp.einsum("tec,th->ech", dispatch.astype(dtype), x.astype(dtype))
    xin = with_logical_constraint(xin, ("expert", None, "embed"))
    gate_h = jax.nn.silu(jnp.einsum("ech,ehm->ecm", xin, w_gate.astype(dtype)))
    up_h = jnp.einsum("ech,ehm->ecm", xin, w_up.astype(dtype))
    hidden = with_logical_constraint(gate_h * up_h, ("expert", None, "mlp"))
    out_e = jnp.einsum("ecm,emh->ech", hidden, w_down.astype(dtype))
    out_e = with_logical_constraint(out_e, ("expert", None, "embed"))

    out = jnp.einsum("tec,ech->th", combine.astype(dtype), out_e)
    return out.astype(x.dtype), aux.astype(jnp.float32)


def moe_ffn_gather(x, router_w, w_gate, w_up, w_down, *,
                   num_experts_per_token: int = 2,
                   dtype=jnp.bfloat16) -> jax.Array:
    """Exact (capacity-free) MoE for SMALL token counts — decode steps.

    Gathers each token's k expert weight slices directly instead of the
    dispatch/combine capacity machinery: no token is ever dropped, so a
    single decoded token is computed exactly. O(T*k*h*m) weight-gather
    memory — right for T = max_batch decode slots, wrong for
    prefill-sized T (use moe_ffn there).
    """
    k = num_experts_per_token
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)                 # [T,k]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    wg = w_gate[idx].astype(dtype)                           # [T,k,h,m]
    wu = w_up[idx].astype(dtype)
    wd = w_down[idx].astype(dtype)                           # [T,k,m,h]
    xin = x.astype(dtype)
    g = jax.nn.silu(jnp.einsum("th,tkhm->tkm", xin, wg))
    u = jnp.einsum("th,tkhm->tkm", xin, wu)
    out = jnp.einsum("tkm,tkmh->tkh", g * u, wd)
    out = jnp.einsum("tkh,tk->th", out, gate_vals.astype(dtype))
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# The dropless layer: one chip's share of the experts
# ---------------------------------------------------------------------------
# What expert parallelism asks of a chip: route every token over ALL the
# experts, compute the terms of the experts that live here, hand the partial
# sum on.  No capacity factor: a token routed to a held expert is computed
# whatever the routing, and the static shapes come from a bound the routing
# cannot exceed (a token sends at most min(k, held) rows here).  The rows
# are sorted by expert into ops/grouped_matmul.py's layout and the three
# expert matmuls are grouped matmuls, which cost by the rows that are there.
#
# Rows move by GATHER in both directions (an XLA scatter of a hundred
# thousand rows is a serial loop on the TPU), and all four movers are one
# function, ops/row_gather.py's `gather_sum`: an out row names the table's
# rows it takes.  Placing rows in expert order reads x by the BUFFER (a row
# takes its token, one slot a row), and so does its transpose's transpose,
# dy at every row's token; the weighted sum back reads the experts' output
# by the TOKEN (its held assignments' rows, k slots a token of which this
# chip's experts fill a few), and so does placing's transpose.  By the
# token, XLA's k gathers cost by the slots; the kernel costs by the rows
# that are there (`gather_sum` takes it where an out row has slots to
# spare, k > 1; at one slot a row XLA's one gather stays).  `pos` [T, k] is
# where an assignment's row lies, `src` [rows] which assignment a row
# holds: one is the other's inverse, the first from a running count an
# expert, the second from a stable sort by expert.

class _Lists(NamedTuple):
    """How the rows lie, read both ways (every leaf integer or bool)."""
    src: jax.Array          # [rows] the assignment (token * k + slot) a row
    #                         of the buffer holds
    row_valid: jax.Array    # [rows] bool: a row some assignment fills
    pos: jax.Array          # [T, k] the buffer row of an assignment
    held: jax.Array         # [T, k] bool: an assignment this chip computes
    gap: jax.Array          # [T * k] the slots NOT held before it, in its token
    held_rows: jax.Array    # [T * k] pos, a token's held assignments first
    held_count: jax.Array   # [T] how many a token has


def _shifted(a, n: int):
    """a [m] -> b [m], b[i] = a[i + n]; zero where a has no such entry."""
    if n == 0:
        return a
    fill = jnp.zeros((abs(n),), a.dtype)
    return jnp.concatenate([a[n:], fill] if n > 0 else [fill, a[:n]])


def _slots_before(flags, k: int):
    """flags [T * k] bool, a token's k slots together -> how many of the
    slots BEFORE it in its token are set, int32.  Shifts of the flat list:
    a [T, k] array would lie k to a tile of 128 lanes."""
    slot = jnp.arange(flags.shape[0], dtype=jnp.int32) % k
    flags = flags.astype(jnp.int32)
    return sum(jnp.where(slot >= back, _shifted(flags, -back), 0)
               for back in range(1, k)) + jnp.zeros_like(flags)


def _held_first(values, held, gap, k: int):
    """values [T * k] -> the same, each token's held slots moved to the
    front of its k in their order (what lies behind them is not defined):
    a held slot moves down by the slots not held before it."""
    out = jnp.zeros_like(values)
    for down in range(k):
        out = jnp.where(_shifted(held & (gap == down), down),
                        _shifted(values, down), out)
    return out


def _rows_at_tokens(table, lists: _Lists):
    """table [T, h] -> [rows, h]: every buffer row its token's row of the
    table, padding rows zero."""
    k = lists.pos.shape[1]
    return row_gather.gather_sum(table, lists.src // k,
                                 lists.row_valid.astype(jnp.int32))


def _sum_held_rows(table, lists: _Lists, gates=None):
    """table [rows, h] -> [T, h] in its dtype: the sum over a token's held
    assignments, in slot order and in float32, of (gate x) its row."""
    k = lists.pos.shape[1]
    weights = None if gates is None else _held_first(
        gates.reshape(-1).astype(jnp.float32), lists.held.reshape(-1),
        lists.gap, k)
    return row_gather.gather_sum(table, lists.held_rows, lists.held_count,
                                 weights)


@jax.custom_vjp
def _place(x, lists: _Lists):
    """x [T, h] -> the rows in expert order [rows, h]; padding rows zero."""
    return _rows_at_tokens(x, lists)


def _place_fwd(x, lists):
    return _place(x, lists), lists


def _place_bwd(lists, d_rows):
    return _sum_held_rows(d_rows, lists), None


_place.defvjp(_place_fwd, _place_bwd)


@jax.custom_vjp
def _combine(out, gates, lists: _Lists):
    """sum over a token's held assignments of gate x its row of `out`
    [rows, h] -> [T, h] in out's dtype, summed in float32."""
    return _sum_held_rows(out, lists, gates)


def _combine_fwd(out, gates, lists):
    return _combine(out, gates, lists), (out, gates, lists)


def _combine_bwd(res, dy):
    out, gates, lists = res
    # dy at every row's token, read once: times the row's gate it is the
    # row's cotangent, times the row itself the gate's
    dy_rows = _rows_at_tokens(dy, lists).astype(jnp.float32)
    d_out = jnp.where(lists.row_valid[:, None],
                      dy_rows * gates.reshape(-1)[lists.src][:, None],
                      0.0).astype(out.dtype)
    d_gate_rows = jnp.sum(dy_rows * out.astype(jnp.float32), axis=-1)
    d_gates = jnp.where(
        lists.held,
        d_gate_rows[jnp.minimum(lists.pos, out.shape[0] - 1)], 0.0)
    return d_out, d_gates, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def select_experts(scores, select_bias, *, num_experts_per_token: int,
                   gate_rule: str, scale: float = 1.0):
    """The selection every router here ends in: scores [T, experts] float32
    (a sigmoid's or a softmax's, whatever made them) -> a token's experts,
    the top k of scores + bias (the bias, where there is one, enters the
    selection only and gets no gradient; with none the top k of the scores
    themselves), and its gates by the stated rule: "renormalised",
    scores[sel] / sum(scores[sel]) x scale, or "raw", scores[sel] as they
    are (at k = 1 a renormalised gate is the constant `scale` and the
    router behind it gets no gradient).
    -> (expert index [T, k] int32, gates [T, k] float32)."""
    if select_bias is None:
        picked, idx = jax.lax.top_k(scores, num_experts_per_token)
    else:
        _, idx = jax.lax.top_k(
            scores + jax.lax.stop_gradient(select_bias.astype(jnp.float32)),
            num_experts_per_token)
        picked = jnp.take_along_axis(scores, idx, axis=-1)
    if gate_rule == "renormalised":
        gates = picked / jnp.sum(picked, axis=-1, keepdims=True) * scale
    elif gate_rule == "raw":
        gates = picked
    else:
        raise ValueError(f"unknown gate_rule {gate_rule!r}; expected "
                         "'renormalised' or 'raw'")
    return idx.astype(jnp.int32), gates


def sigmoid_route(x, router_w, select_bias, *, num_experts_per_token: int,
                  scale: float):
    """The bias-corrected sigmoid router: s = sigmoid(x W_r) in float32 at
    full matmul precision (a bfloat16 pass flips near-ties); a token's
    experts are the top k of s + bias; its gates s[sel] / sum(s[sel]) x
    scale (`select_experts`).
    -> (expert index [T, k] int32, gates [T, k] float32)."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    return select_experts(
        scores, select_bias, num_experts_per_token=num_experts_per_token,
        gate_rule="renormalised", scale=scale)


def softmax_route(x, router_w, *, num_experts_per_token: int, scale: float):
    """The softmax router: p = softmax(x W_r) over all the experts in
    float32 at full matmul precision (as `sigmoid_route`, and for its
    reason); a token's experts are the top k of p; its gates p[sel] /
    sum(p[sel]) x scale.  No selection bias.
    -> (expert index [T, k] int32, gates [T, k] float32)."""
    probs = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    return select_experts(
        probs, None, num_experts_per_token=num_experts_per_token,
        gate_rule="renormalised", scale=scale)


def routed_experts(x, idx, gates, w_gate, w_up, w_down, *,
                   experts_held: Tuple[int, int], dtype=jnp.bfloat16,
                   tile_m=None, usual_rows: Optional[int] = None):
    """The held experts' part of a routed-expert layer, dropless.

    x [T, h]; idx, gates [T, k]: every token's experts (indices over ALL
    the model's experts) and gates, from the router; experts_held = (first,
    count): this chip holds experts first .. first + count - 1, whose
    SwiGLU weights are w_gate, w_up [count, h, m] and w_down [count, m, h].
    -> (y [T, h] = sum over a token's HELD experts e of
        gate_e (silu(x Wg_e) * (x Wu_e)) Wd_e,
    stats).  w_gate None: the experts are UNGATED, two matrices each, and a
    term is gate_e relu(x Wu_e)^2 Wd_e (`common.relu2`): two grouped
    matmuls a layer, not three.  What the other experts would add is not here: it is the other
    chips'.  stats: `rows_held` (assignments that landed here), `load_max`
    and `load_mean` over the held experts, `rows_bound` (the buffer's
    bound, static), int32 / float32 scalars.

    The buffer of rows in expert order is as long as the bound, tokens x
    min(k, count), which the routing cannot exceed.  The movers by the token
    (the sum back, placing's transpose) cost by the rows that are there
    where k > 1 (`ops/row_gather.py`; `dispatch.taken()["routed_experts"]`
    says which way they went, `["routed_experts.plan"]` the slots, the
    buffer and the bound of each static size traced); the movers by the
    buffer (XLA's one gather), the index arithmetic and the SwiGLU between
    the grouped matmuls cost by the buffer, not by the rows in it.
    `usual_rows`, where given and under the
    bound: a step whose rows fit that many takes a buffer of that length
    instead (`lax.cond` on the count: the same computation at two static
    sizes, every row computed in either).
    """
    from ray_tpu.ops import grouped_matmul as gm

    first, count = experts_held
    if w_up.shape[0] != count:
        raise ValueError(f"{w_up.shape[0]} experts' weights, {count} held")
    tokens, k = idx.shape
    tile_m = tile_m or gm.TILE_M
    bound = tokens * min(k, count)

    with jax.named_scope(common.MOE_DISPATCH):
        local = idx - first
        held = (local >= 0) & (local < count)
        key = jnp.where(held, local, count).reshape(-1)     # [T * k]
        onehot = (key[:, None] == jnp.arange(count, dtype=jnp.int32)[None, :]
                  ).astype(jnp.int32)
        sizes = jnp.sum(onehot, axis=0)
        # an assignment's rank among its expert's, in token order
        rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot,
                       axis=1)
        # the inverse: a stable sort by expert lists the assignments as the
        # buffer holds them, less the padding between groups
        order = jnp.argsort(key, stable=True).astype(jnp.int32)

        # a token's held assignments first among its k slots: the lists
        # the movers by the token walk
        gap = _slots_before(~held.reshape(-1), k)
        held_count = jnp.sum(held, axis=1, dtype=jnp.int32)

    def through_the_experts(rows_bound: int):
        """The layer over a buffer that holds `rows_bound` rows."""
        rows = gm.layout_rows(rows_bound, count, tile_m)
        way = row_gather.path(x.shape[1], k, dtype)
        dispatch.record("routed_experts", way)
        dispatch.record(
            "routed_experts.plan",
            f"{'rows_by_gather' if way == 'xla' else 'rows_by_index'},"
            f"slots{tokens * k},buffer{rows},entries<={rows_bound}")
        with jax.named_scope(common.MOE_DISPATCH):
            layout = gm.group_layout(sizes, rows, tile_m)
            pos = layout.starts[jnp.minimum(key, count - 1)] + rank
            row_group, row_valid = gm.row_groups(layout)
            packed = (jnp.cumsum(sizes) - sizes)[row_group] + (
                jnp.arange(rows, dtype=jnp.int32) - layout.starts[row_group])
            src = order[jnp.clip(packed, 0, tokens * k - 1)]
            lists = _Lists(src, row_valid, pos.reshape(tokens, k), held, gap,
                           _held_first(pos, held.reshape(-1), gap, k),
                           held_count)
            rows_in = _place(x.astype(dtype), lists)
        with jax.named_scope(common.MOE_EXPERTS):
            if w_gate is None:
                hidden = common.relu2(
                    gm.grouped_matmul(rows_in, w_up.astype(dtype), layout))
            else:
                gate_h = gm.grouped_matmul(rows_in, w_gate.astype(dtype),
                                           layout)
                up_h = gm.grouped_matmul(rows_in, w_up.astype(dtype), layout)
                hidden = jax.nn.silu(gate_h) * up_h
            out = gm.grouped_matmul(hidden, w_down.astype(dtype), layout)
        with jax.named_scope(common.MOE_COMBINE):
            return _combine(out, gates, lists)

    rows_held = jnp.sum(sizes)
    if usual_rows is None or usual_rows >= bound:
        y = through_the_experts(bound)
    else:
        # each side under its own checkpoint: a cond's backward keeps BOTH
        # sides' residuals as outputs, and the buffers are the large ones.
        # The conditional itself (its branch, what it copies in and out)
        # counts as dispatch: choosing the buffer is placing the rows.
        with jax.named_scope(common.MOE_DISPATCH):
            y = jax.lax.cond(
                rows_held <= usual_rows,
                jax.checkpoint(lambda: through_the_experts(usual_rows)),
                jax.checkpoint(lambda: through_the_experts(bound)))

    stats = {"rows_held": rows_held, "load_max": jnp.max(sizes),
             "load_mean": jnp.mean(sizes.astype(jnp.float32)),
             "rows_bound": jnp.asarray(bound, jnp.int32)}
    return y.astype(x.dtype), stats
