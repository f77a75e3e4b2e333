"""The layer stack under the segmented model files (models/hybrid.py,
latent_moe.py, swa_moe.py, gdn_moe.py, cca_moe.py): how a model of segments
is laid out, walked and scored, written once.  It takes a model's functions,
never a model's name; what a model file keeps is its config, `segments`,
`_layer_shapes`, the draws only it has, `_layer` and its mechanisms, what
its `forward_hidden` does before the layers, and the final norm.

The parameter tree.  A model's layers are SEGMENTS, runs of `repeats`
repetitions of a PATTERN of kinds of layer, beginning at layer `first`:
`params["layers"][segNN][position in the pattern][leaf][repeat]`, a
position's layers stacked on a leading repeats axis so that a segment is one
scan.  The expert files' segments are runs of ONE kind (`one_kind`: the
position is "0"), models/hybrid.py's are (mamba, window) pairs.  A kind's
leaves are a table, `layer_shapes(kind, config)`: name -> (shape, logical
axes, init), init a matrix's fan-in or the name of a draw.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import common, moe
from ray_tpu.parallel.sharding import with_logical_constraint

F32 = jnp.float32
Patterns = List[Tuple[Tuple[Any, ...], int, int]]


def segment_name(i: int) -> str:
    return f"seg{i:02d}"


def runs(kinds) -> List[Tuple[Any, int, int]]:
    """A model's kinds of layer in order -> (kind, first layer, repeats):
    maximal runs of layers of one kind."""
    out: List[Tuple[Any, int, int]] = []
    for i, kind in enumerate(kinds):
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1], out[-1][2] + 1)
        else:
            out.append((kind, i, 1))
    return out


def one_kind(segments: Callable[[Any], List[Tuple[Any, int, int]]]):
    """A model's `segments(config)` -> [(kind, first, repeats)], runs of ONE
    kind, as the function that gives them as patterns of one."""
    return lambda config: [((kind,), first, repeats)
                           for kind, first, repeats in segments(config)]


# ---------------------------------------------------------------------------
# The parameter tree
# ---------------------------------------------------------------------------

_DRAWS = {
    "ones": lambda key, shape: jnp.ones(shape),
    "zeros": lambda key, shape: jnp.zeros(shape),
    # a matrix: normal x (1 / sqrt(fan-in)).  A model whose seeds' values
    # were drawn as normal / sqrt(fan-in) hands that rule in under this
    # name: the two round differently, and the values are the seed's
    "fan_in": lambda key, shape, fan_in: (
        jax.random.normal(key, shape) * (1.0 / math.sqrt(fan_in))),
    # a sublayer's lane leaves (`hc_shapes`): the three scales of the
    # dynamic part, and the static part
    "hc_scale": lambda key, shape: (
        jnp.asarray(HC_SCALE[:shape[0]])
        * (1.0 + 0.1 * jax.random.normal(key, shape))),
    "hc_base": lambda key, shape: hc_base(key, shape),
    "hc_pre_base": lambda key, shape: jnp.full(
        shape, -math.log(shape[0] - 1.0)),
}
# The lanes' draws.  With w_hc = 0 the lanes do nothing (`residual`): pre
# sums to 1 (base -ln(n - 1) under the sigmoid), post is 1 (base 0), comb is
# doubly stochastic whatever its base.  The seeded weights are drawn so that
# each part of the mixing carries weight in a comparison with a plain
# reference: w_hc normal / sqrt(n d), so m is about normal(0, 1) a token;
# scales of 0.5, 0.5 and 1 (x (1 + 0.1 normal)), so the DYNAMIC part moves
# pre and post by tens of per cent and comb's logits by about 1; the
# diagonal of base's matrix part 2 x (1 + 0.1 normal), so the STATIC part
# keeps about 0.7 of a lane in its lane; and rows of such logits are far
# enough from doubly stochastic that ONE round where twenty are due shows.
HC_SCALE = (0.5, 0.5, 1.0)
HC_DIAGONAL = 2.0


def hc_base(key, shape):
    """base [2 n + n^2]: -ln(n - 1) for pre, 0 for post, the matrix part
    a drawn diagonal."""
    n = math.isqrt(shape[0] + 1) - 1
    pre = jnp.full((n,), -math.log(n - 1.0))
    diagonal = HC_DIAGONAL * (1.0 + 0.1 * jax.random.normal(key, (n,)))
    return jnp.concatenate([pre, jnp.zeros((n,)),
                            jnp.diag(diagonal).reshape(-1)])


def swiglu_shapes(prefix: str, h: int, m: int, experts=None):
    """The table's rows of a SwiGLU's three matrices, `<prefix>_gate`, `_up`
    [h, m] and `_down` [m, h]; of `experts` of them stacked where given."""
    lead, axis = ((), ()) if experts is None else ((experts,), ("expert",))
    return {f"{prefix}_gate": (lead + (h, m), axis + ("embed", "mlp"), h),
            f"{prefix}_up": (lead + (h, m), axis + ("embed", "mlp"), h),
            f"{prefix}_down": (lead + (m, h), axis + ("mlp", "embed"), m)}


def hc_shapes(prefix: str, h: int, n: int):
    """The table's rows of ONE sublayer's lane leaves (ops/
    hyper_connection.py): `<prefix>_w` [n h, 2 n + n^2], `_scale` [3],
    `_base` [2 n + n^2], float32 whatever the compute dtype."""
    width = 2 * n + n * n
    return {f"{prefix}_w": ((n * h, width), ("embed", None), n * h),
            f"{prefix}_scale": ((3,), (None,), "hc_scale"),
            f"{prefix}_base": ((width,), (None,), "hc_base")}


def hc_head_shapes(h: int, n: int):
    """.. and of the collapse behind the last layer, a GROUP of the top
    table: `w` [n h, n], `scale` [1], `base` [n]."""
    return {"w": ((n * h, n), ("embed", None), n * h),
            "scale": ((1,), (None,), "hc_scale"),
            "base": ((n,), (None,), "hc_pre_base")}


def relu2_shapes(prefix: str, h: int, m: int, experts=None):
    """The table's rows of an UNGATED feed-forward's two matrices,
    `<prefix>_up` [h, m] and `_down` [m, h] (`common.relu2_mlp`, and
    `routed_part` with no gate matrix); of `experts` of them stacked where
    given."""
    shapes = swiglu_shapes(prefix, h, m, experts)
    del shapes[f"{prefix}_gate"]
    return shapes


class Params:
    """A model's parameter tree from its functions: `patterns(config)` ->
    [(pattern, first, repeats)]; `layer_shapes(kind, config)`, a kind's
    table; `top_shapes(config)`, the table of the leaves beside "layers"
    (embedding, final norm, head); `draws`, the draws the tables name (name
    -> fn(key, shape), float32) beside "ones", "zeros" and a fan-in."""

    def __init__(self, patterns: Callable[[Any], Patterns],
                 layer_shapes: Callable[[Any, Any], Dict[str, Tuple]],
                 top_shapes: Callable[[Any], Dict[str, Tuple]],
                 draws: Optional[Mapping[str, Callable]] = None):
        self.patterns, self.layer_shapes = patterns, layer_shapes
        self.top_shapes, self.draws = top_shapes, {**_DRAWS, **(draws or {})}

    def draw(self, key, shape, init, dtype):
        """One leaf: init a draw's name, or a matrix's fan-in."""
        if isinstance(init, str):
            return self.draws[init](key, shape).astype(dtype)
        return self.draws["fan_in"](key, shape, init).astype(dtype)

    def init(self, config, keys: Mapping[str, Any]) -> Dict[str, Any]:
        """The parameters.  `keys`: the key of every top leaf that draws
        one and of "layers", as the MODEL splits its key.  Layer i (first +
        rep x len(pattern) + its position) draws from fold_in(keys["layers"],
        i), one split of that a leaf in the table's order, whether the
        leaf's draw uses it or not; a group's leaves likewise from ITS
        key."""
        def layer(kind, index):
            shapes = self.layer_shapes(kind, config)
            split = jax.random.split(
                jax.random.fold_in(keys["layers"], index), len(shapes))
            return {name: self.draw(k, shape, init, config.param_dtype)
                    for k, (name, (shape, _, init))
                    in zip(split, shapes.items())}

        return {"layers": self._layers(
            config, lambda kind, at, stride, repeats: jax.tree.map(
                lambda *a: jnp.stack(a),
                *[layer(kind, at + rep * stride) for rep in range(repeats)])),
            **self._top(config, lambda name, spec, key: self.draw(
                key, spec[0], spec[2], config.param_dtype), keys)}

    def _top(self, config, leaf, keys: Optional[Mapping[str, Any]] = None):
        """{name: leaf(name, spec, key)} over the top table, a group a dict
        of the same; `keys[name]` is a leaf's key, and a group's, of which
        each of its leaves gets one split."""
        def entry(name, spec, key):
            if not isinstance(spec, dict):
                return leaf(name, spec, key)
            split = [None] * len(spec) if key is None \
                else jax.random.split(key, len(spec))
            return {n: entry(n, s, k)
                    for k, (n, s) in zip(split, spec.items())}

        return {name: entry(name, spec, (keys or {}).get(name))
                for name, spec in self.top_shapes(config).items()}

    def _layers(self, config, position):
        """{segNN: {position in the pattern: position(kind, its first
        layer, the layers to its next repetition, repeats)}}."""
        return {
            segment_name(si): {
                str(pos): position(kind, first + pos, len(pattern), repeats)
                for pos, kind in enumerate(pattern)}
            for si, (pattern, first, repeats)
            in enumerate(self.patterns(config))}

    def tree(self, config, leaf: Callable[[str, Tuple], Any]):
        """The parameters' tree with leaf(name, (shape, logical axes, init))
        at every leaf, a layer leaf's shape and axes those of the STACKED
        leaf: (repeats, ..) and ("layers", ..)."""
        return {"layers": self._layers(
            config, lambda kind, at, stride, repeats: {
                name: leaf(name, ((repeats,) + shape, ("layers",) + axes,
                                  init))
                for name, (shape, axes, init)
                in self.layer_shapes(kind, config).items()}),
            **self._top(config, lambda name, spec, key: leaf(name, spec))}

    def logical_axes(self, config) -> Dict[str, Any]:
        """Logical-axis tree matching `init`, for parallel.sharding."""
        return self.tree(config, lambda name, spec: spec[1])

    def num_params(self, config) -> int:
        return sum(jax.tree.leaves(self.tree(
            config, lambda name, spec: math.prod(spec[0]))))


# ---------------------------------------------------------------------------
# The walk
# ---------------------------------------------------------------------------

def matmul(x, w, c, out_dtype=None):
    """bf16 operands, fp32 accumulation, the result in the compute dtype
    (`c.dtype`) or `out_dtype`."""
    return jnp.einsum("bsi,io->bso", x.astype(c.dtype), w.astype(c.dtype),
                      preferred_element_type=out_dtype or c.dtype)


def per_head(x, heads: int, fn):
    """fn over every head's columns of x [b, s, heads x w], by whole tiles
    (`common.by_tiles`): fn sees [b, s / 8, heads, 8, w] float32 and gives
    the like; the result in x's dtype."""
    return common.from_tiles(fn(common.by_tiles(x, heads).astype(F32))
                             .astype(x.dtype))


@functools.cache
def layer_fn(layer, kind, config):
    """`layer(.., kind=kind, c=config)` under the config's remat: ONE
    function object a (layer, kind, config), so JAX traces a kind of layer
    once for every segment that holds it, not once a segment.  The one
    place that decides what a layer's checkpoint keeps."""
    return common.maybe_remat(functools.partial(layer, kind=kind, c=config),
                              config.remat, config.remat_policy)


def walk(layer, config, segments, layers, carry, beside, first: int = 0):
    """One scan a segment of one kind over `layers` (`params["layers"]`;
    `segments` the model's from its `first` on, where a model walks its
    stack in two goes):
    `layer(carry, a layer's leaves, beside(kind), kind=, c=) -> (carry, an
    expert layer's routing counts or None)`, the carry of any shape,
    `beside(kind)` what rides beside it (rope tables).  -> (carry, the LAST
    expert layer's counts with `rows_held_all_layers`, the rows ALL the
    expert layers held together; None without an expert layer)."""
    stats = rows_held = None
    for si, (kind, _, _) in enumerate(segments):
        fn, extra = layer_fn(layer, kind, config), beside(kind)

        def body(carry, lp, fn=fn, extra=extra):
            return fn(carry, lp, extra)

        carry, per_layer = jax.lax.scan(
            body, carry, layers[segment_name(first + si)]["0"])
        if per_layer is not None:
            stats = jax.tree.map(lambda a: a[-1], per_layer)
            held = jnp.sum(per_layer["rows_held"])
            rows_held = held if rows_held is None else rows_held + held
    if stats is not None:
        stats["rows_held_all_layers"] = rows_held
    return carry, stats


def hyper(config):
    """The lanes' settings (`ops.hyper_connection.HC`) of a config whose
    residual stream has several (`hc_mult`), else None."""
    from ray_tpu.ops.hyper_connection import HC

    if not getattr(config, "hc_mult", None):
        return None
    return HC(config.hc_mult, config.hc_sinkhorn_iters, config.hc_eps,
              config.rms_norm_eps, config.mhc_h_res_clamp_min,
              config.mhc_h_res_clamp_max)


def residual(x, sublayer, lp, prefix: str, config, mixes=None):
    """A sublayer round the residual stream: x + sublayer(x), `sublayer`
    norm and all, where the stream is one lane.  Where it is several
    (`hyper`), the two calls of ops/hyper_connection.py round it with the
    sublayer's own lane leaves `lp[<prefix>_w | _scale | _base]`, under the
    scope `resid.mix` and outside the sublayer's: x [b, s, n h], the
    sublayer sees the lanes' weighted sum [b, s, h]; the call's `mix` is
    appended to `mixes` where a list is given (`comb_row_err` reads it).
    With w = 0 and the draws of `hc_shapes` equal lanes stay equal and each
    is the one-lane stream."""
    hc = hyper(config)
    if hc is None:
        return with_logical_constraint(x + sublayer(x),
                                       ("batch", "seq", "embed"))
    from ray_tpu.ops.hyper_connection import hc_post, hc_pre

    with jax.named_scope(common.RESID_MIX):
        u, mix, x = hc_pre(x, lp[prefix + "_w"], lp[prefix + "_scale"],
                           lp[prefix + "_base"], hc)
    if mixes is not None:
        mixes.append(mix)
    y = sublayer(u)
    with jax.named_scope(common.RESID_MIX):
        return with_logical_constraint(hc_post(x, y, mix, hc),
                                       ("batch", "seq", "embed"))


def comb_row_err(mixes, config):
    """The worst |row sum - 1| of the lanes' mixing matrix over the tokens
    of these calls' `mix`: what the Sinkhorn rounds leave (its columns are
    the last thing they divide)."""
    from ray_tpu.ops.hyper_connection import mix_parts

    return functools.reduce(jnp.maximum, [
        jnp.max(jnp.abs(jnp.sum(mix_parts(mix, config.hc_mult)[2], -1) - 1.0))
        for mix in mixes])


def routed_part(flat, route, w_gate, w_up, w_down, config, usual_load: int):
    """The router and models/moe.py's dropless layer for ONE CHIP'S SHARE
    of the experts: the program holds `config.experts_held` (first, how
    many) of the `config.router_width` the model routes over.  The router
    and the top-k run over all of them; the held experts' terms are
    computed, what the absent ones would add is left out, and that partial
    result goes on (expert parallelism without its exchange).  flat [T,
    hidden] -> (the held experts' sum, the routing counts, and whatever
    `route()` gives after (expert index [T, k], gates [T, k])).  The usual
    buffer holds `usual_load` times the rows even routing sends here; a
    step that sends more takes the full bound's.  `w_gate` None: ungated
    two-matrix experts (`moe.routed_experts`)."""
    with jax.named_scope(common.MOE_ROUTE):
        idx, gates, *state = route()
    even = -(-flat.shape[0] * config.num_experts_per_tok
             * config.experts_held[1] // config.router_width)
    y, stats = moe.routed_experts(
        flat, idx, gates, w_gate, w_up, w_down,
        experts_held=config.experts_held, dtype=config.dtype,
        usual_rows=usual_load * even)
    return (y, stats, *state)


# ---------------------------------------------------------------------------
# A partial rope's way to the flash kernels, whose rope turns column i with
# column i + d/2 over the WHOLE head: a head's columns reordered at use so
# that the rotary pairs lie so, and tables with an identity tail
# ---------------------------------------------------------------------------

def rope_tables(seq: int, width: int, theta: float):
    """(cos, sin) [seq, width / 2] float32 at positions 0 .. seq - 1, the
    plain frequencies theta^(-2i / width)."""
    inv_freq = 1.0 / float(theta) ** (
        2.0 * jnp.arange(width // 2, dtype=F32) / width)
    angle = jnp.arange(seq, dtype=F32)[:, None] * inv_freq[None, :]
    return jnp.cos(angle), jnp.sin(angle)


def kernel_tables(cos, sin, head_dim: int):
    """The rotary pairs' tables [seq, r / 2] as the flash kernels take them
    for a head ordered by `rotary_first`: [seq, head_dim / 2], then cos 1
    and sin 0 for the pairs that pass through."""
    seq, passing = cos.shape[0], head_dim // 2 - cos.shape[1]
    if not passing:
        return cos, sin
    return (jnp.concatenate([cos, jnp.ones((seq, passing), F32)], axis=1),
            jnp.concatenate([sin, jnp.zeros((seq, passing), F32)], axis=1))


def rope_word(kind: str, r: int, head_dim: int) -> str:
    """What `dispatch.taken()["<model>.rope"]` says of a kind of layer."""
    return f"{kind}:in_kernel{r}of{head_dim}" + (
        "" if r == head_dim else "_columns_reordered_at_use_identity_tail")


def rotary_first(x, heads: int, r: int):
    """The last axis, heads x d with a head's columns as published, [rot_a
    | rot_b | pass] (r/2, r/2, d - r; the rotary pair i being (rot_a[i],
    rot_b[i])) -> every head's as [rot_a | pass' | rot_b | pass''], the
    pass-through columns cut in two: pair i is then (i, i + d/2) of the
    whole head.  A weight's columns or an activation's; exact in any dtype,
    and the gradient is the permutation back.  Where the rope turns half
    the head the four parts are quarters and it is one transpose."""
    lead, d = x.shape[:-1], x.shape[-1] // heads
    if r == d:
        return x
    if r % 2 or (d - r) % 2:
        raise ValueError(f"rope pairs columns and the rest is cut in two: "
                         f"it turns {r} of {d}")
    if 2 * r == d:
        x = x.reshape(*lead, heads, 2, 2, d // 4)   # [rot | pass, a | b, .]
        return jnp.swapaxes(x, -3, -2).reshape(*lead, heads * d)
    if heads > 1:       # one head's columns are sliced as they lie: a unit
        x = x.reshape(*lead, heads, d)          # axis would move the program
    cut = r + (d - r) // 2
    return jnp.concatenate([x[..., :r // 2], x[..., r:cut],
                            x[..., r // 2:r], x[..., cut:]],
                           axis=-1).reshape(*lead, heads * d)


# ---------------------------------------------------------------------------
# The loss tail
# ---------------------------------------------------------------------------

class LossTail:
    """A model's public scoring functions from its `forward_hidden(params,
    tokens, config) -> (normed hidden states [b, s, hidden], the routing
    counts or None)` and the name of the leaf that is its output head
    ([vocab, hidden]: "lm_head", or "tok_embed" where tied).  A model module
    binds its public names from one of these.  `second(params, tokens,
    next_tokens, config)`, where a model has a block BEHIND the stack that
    re-reads the embedding and the head (multi-token prediction at depth 1,
    DeepSeek-V3's report, section 2.2), gives None for a config without the
    block, else (what `forward_hidden` gives, the block's normed hidden
    states from the tokens one position on, the second loss's weight): a
    position's objective is then nll_main[i] + weight x nll_second[i],
    nll_second[i] = -log p_block(tokens[i+2] | tokens[:i+2]) and zero at
    the last position, which has no such token."""

    def __init__(self, forward_hidden, head: str,
                 second: Optional[Callable] = None):
        self.forward_hidden, self.head = forward_hidden, head
        self.second = second

    def forward(self, params, tokens, config):
        """tokens [b, s] int32 -> logits [b, s, vocab] (fp32)."""
        x, _ = self.forward_hidden(params, tokens, config)
        return common.tied_logits(x, params[self.head], config.dtype)

    def _nll(self, x, params, targets, config):
        if config.fused_ce:
            return common.fused_nll(x, params[self.head], targets)
        logits = common.tied_logits(x, params[self.head], config.dtype)
        return common.logits_nll(logits, targets)

    def _nll_and_stats(self, params, batch, config):
        """(a position's objective [b, s], the routing counts or None, the
        second loss's mean or None)."""
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        both = self.second and self.second(params, inputs, targets, config)
        if not both:
            x, stats = self.forward_hidden(params, inputs, config)
            return self._nll(x, params, targets, config), stats, None
        (x, stats), x2, weight = both
        nll = self._nll(x, params, targets, config)
        # the block sees position i's stream and token i + 1 and scores
        # token i + 2; the last position has none: any id there, masked
        ahead = jnp.concatenate([targets[:, 1:], targets[:, :1]], axis=1)
        nll2 = self._nll(x2, params, ahead, config)
        with jax.named_scope(common.LOSS):
            s = targets.shape[1]
            nll2 = jnp.where(jnp.arange(s) < s - 1, nll2, 0.0)
            return (nll + weight * nll2, stats,
                    jnp.sum(nll2) / max(nll2.size - nll2.shape[0], 1))

    def token_nll(self, params, batch, config):
        """A position's objective: -log p(tokens[t+1] | tokens[:t+1]) (plus
        the weighted second loss where the config has one) for every
        position: [b, s] fp32.  batch: {"tokens": [b, s+1] int32}."""
        return self._nll_and_stats(params, batch, config)[0]

    def loss_and_metrics(self, params, batch, config):
        """(the mean objective, the LAST expert layer's routing counts
        and `rows_held_all_layers` as `moe_*` device scalars, which
        `ShardedTrainStep` carries in the step's metrics, none without an
        expert layer; `mtp_nll`, the second loss's mean over the positions
        that have one)."""
        nll, stats, second = self._nll_and_stats(params, batch, config)
        mask = batch.get("mask")
        loss = common.masked_mean(nll, None if mask is None else mask[:, 1:])
        metrics = {f"moe_{k}": v for k, v in (stats or {}).items()}
        if second is not None:
            metrics["mtp_nll"] = second
        return loss, metrics

    def loss_fn(self, params, batch, config):
        """The mean of `token_nll`, over the positions batch["mask"] keeps
        if there is one."""
        return self.loss_and_metrics(params, batch, config)[0]
