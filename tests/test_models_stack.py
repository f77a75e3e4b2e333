"""models/stack.py: the layer stack the five segmented model files share.

The first half is the net under the move (PR 47), written on PR 46's tree
before any model file was touched: what `init_params`, `logical_axes`,
`not_trained` and `num_params` gave THEN, as digests.  A parameter that is
drawn from another key, stacked in another order, named, shaped or sharded
otherwise moves a digest; a change that means to replaces it here and says
so.  (The compiled step programs have theirs in tests/test_tpu_aot_compile
.py's `PARENT_HLO_SHA256` and tests/test_tpu_aot_compile_cca.py.)
"""

import dataclasses
import hashlib
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import common, stack

# module -> its config class
MODULES = {"hybrid": "HybridConfig", "latent_moe": "LatentMoEConfig",
           "swa_moe": "SwaMoEConfig", "gdn_moe": "GdnMoEConfig",
           "cca_moe": "CcaMoEConfig"}
# cell -> its configuration under benchmark/configs/
CELLS = {"train-hybrid-d8": "phi4-mini-flash-train-d8.json",
         "train-moe-mla-d6": "kanana-2-30b-a3b-train-d6e16.json",
         "train-swa-moe-d5": "laguna-s-2.1-train-d5e8.json",
         "train-gdn-moe-d4": "qwen3-next-80b-a3b-train-d4e32.json",
         "train-cca-moe-d4": "zaya1-8b-train-d4.json"}


def _digest(tree, show, is_leaf=None) -> str:
    """sha256 over `<path>=<show(leaf)>` of every leaf, in path order."""
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=is_leaf)[0]:
        h.update(jax.tree_util.keystr(path).encode() + b"=")
        h.update(show(leaf))
        h.update(b"\n")
    return h.hexdigest()


def _bits(a) -> bytes:
    a = np.asarray(a)
    return f"{a.dtype}{a.shape}".encode() + a.tobytes()


def tiny_digests(name: str) -> dict:
    """Of a module's tiny config: the parameters' bits at key(0), the trees
    of `logical_axes` and `not_trained` (None where the module has none)
    and `num_params`."""
    mod = importlib.import_module(f"ray_tpu.models.{name}")
    config = getattr(mod, MODULES[name]).tiny()
    frozen = mod.not_trained(config) if hasattr(mod, "not_trained") else None
    return {
        "params": _digest(mod.init_params(config, jax.random.key(0)), _bits),
        "logical_axes": _digest(mod.logical_axes(config),
                                lambda axes: repr(axes).encode(),
                                is_leaf=lambda x: isinstance(x, tuple)),
        "not_trained": frozen and _digest(frozen,
                                          lambda b: repr(b).encode()),
        "num_params": mod.num_params(config),
    }


def cell_digests(cell: str) -> dict:
    """Of a cell's configuration file, by `jax.eval_shape`: every leaf's
    path, shape and dtype, and `num_params`."""
    from benchmark.drivers import train_model

    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "configs", CELLS[cell])
    with open(path) as f:
        doc = json.load(f)
    config = train_model.build_config(doc["program"], doc["model"],
                                      doc["train"])
    mod = importlib.import_module(type(config).__module__)
    shapes = jax.eval_shape(
        lambda: mod.init_params(config, jax.random.key(0)))
    return {
        "shapes": _digest(shapes,
                          lambda a: f"{a.dtype}{a.shape}".encode()),
        "logical_axes": _digest(mod.logical_axes(config),
                                lambda axes: repr(axes).encode(),
                                is_leaf=lambda x: isinstance(x, tuple)),
        "num_params": mod.num_params(config),
    }


# As PR 46's tree (40fa1e4) gave them.
PARENT_TINY = {
    "cca_moe": {
        "logical_axes":
            "4c9152f31ef07a6fdfe369d3b665e6b01db8104a8b23f07ace5880d7f8fd2272",
        "not_trained":
            "4c534133348b2f53b7917f4245f2ca3ba59b033e1444f9e360ec0efe06709187",
        "num_params": 696872,
        "params":
            "9e513e41170d3ee84de3c3db4b48ed43e174cc60d11d0595e4dee9fb3773bf06",
    },
    "gdn_moe": {
        "logical_axes":
            "40313b31a1a08d2a681992380e4526cf0ef54b4d9c8327a138914b43973bda96",
        "not_trained": None,
        "num_params": 329272,
        "params":
            "07280d41c190f21a8ef083e9e05ece1016062269de460266b11e6840ae157cb1",
    },
    "hybrid": {
        "logical_axes":
            "850a5ebb078cf3ba1487b68621b6743d8736760e6ef3395f3279e9e49eac50b0",
        "not_trained": None,
        "num_params": 364864,
        "params":
            "9c1552751111793811dab5d46782927c61c1e06c9e2cfd0e00d925267ba0d214",
    },
    "latent_moe": {
        "logical_axes":
            "c809ca9226a479269b7584adb607589e8a39eb4e97fa36389997c42378b2ada0",
        "not_trained":
            "2dc8538c89230637b0ac3c850bb343bb0862dc4271833881fbd845ea73fabe1f",
        "num_params": 185920,
        "params":
            "51c1fde6718e847c6daf6636b479cfcd94971846e7ee51953d1c89948ddcf7f9",
    },
    "swa_moe": {
        "logical_axes":
            "e095d8bae4d50853b4fc3703a24fcbf4265ea56cb359aaa01f747796a225883e",
        "not_trained": None,
        "num_params": 481600,
        "params":
            "10d7488ab5d6a4b2fdd39a258c8b361c1cfe750218e7660d8c67d9e6b8477db5",
    },
}
PARENT_CELLS = {
    "train-cca-moe-d4": {
        "logical_axes":
            "4c9152f31ef07a6fdfe369d3b665e6b01db8104a8b23f07ace5880d7f8fd2272",
        "num_params": 897477704,
        "shapes":
            "29e7b8eaa876df61f7d62d43d992dce1a6f87e1e04d57b8da316127d340b8ab5",
    },
    "train-gdn-moe-d4": {
        "logical_axes":
            "40313b31a1a08d2a681992380e4526cf0ef54b4d9c8327a138914b43973bda96",
        "num_params": 625667136,
        "shapes":
            "d5b1b8635aa76e59ca8724159b04906263e5b469c6bec3101cc80a26b294852e",
    },
    "train-hybrid-d8": {
        "logical_axes":
            "850a5ebb078cf3ba1487b68621b6743d8736760e6ef3395f3279e9e49eac50b0",
        "num_params": 915311616,
        "shapes":
            "f78bd3fcb54065439a0b36c4bfb56ba50369d185d9a2478c2c27a3698fbf0f33",
    },
    "train-moe-mla-d6": {
        "logical_axes":
            "c809ca9226a479269b7584adb607589e8a39eb4e97fa36389997c42378b2ada0",
        "num_params": 687502976,
        "shapes":
            "60eea52d0098995502660be2cfb31e04afdd14b5fec43b9dc48da31fa904a059",
    },
    "train-swa-moe-d5": {
        "logical_axes":
            "e095d8bae4d50853b4fc3703a24fcbf4265ea56cb359aaa01f747796a225883e",
        "num_params": 811017216,
        "shapes":
            "8f819c4d60736bca2b21066317927427b0c73647433a78e9a9e75ea93664edb8",
    },
}


@pytest.mark.parametrize("name", sorted(MODULES))
def test_the_tiny_parameters_are_the_parent_s_to_the_bit(name):
    assert tiny_digests(name) == PARENT_TINY[name]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_cell_s_parameter_tree_is_the_parent_s(cell):
    assert cell_digests(cell) == PARENT_CELLS[cell]


# ---------------------------------------------------------------------------
# models/stack.py's own cases, on a toy model: the walk, the tree, the tail
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Toy:
    segments: tuple = ()
    hidden: int = 4
    vocab: int = 11
    dtype: type = jnp.float32
    param_dtype: type = jnp.float32
    remat: bool = True
    remat_policy: str = "full"
    fused_ce: bool = False


TRACED = []


def _toy_layer(carry, lp, beside, *, kind, c):
    """x <- x w + beside, the second carry counts layers; a "routed" kind
    reports the counts its leaves hold, any other kind None."""
    TRACED.append(kind)
    x, seen = carry
    stats = None
    if kind.startswith("routed"):
        stats = {"rows_held": lp["held"], "load_max": lp["held"] * 10}
    return (x * lp["w"] + beside, seen + 1), stats


def _toy_layers(segments):
    """segNN -> {"0": {"w": [repeats], "held": [repeats] = the layer's
    index + 1}}."""
    return {stack.segment_name(si): {"0": {
        "w": jnp.full((repeats,), 2.0),
        "held": jnp.arange(first + 1, first + repeats + 1, dtype=jnp.int32)}}
        for si, (_, first, repeats) in enumerate(segments)}


def _walk(segments, x=1.0):
    c = Toy(segments=tuple(segments))
    return stack.walk(_toy_layer, c, segments, _toy_layers(segments),
                      (jnp.float32(x), jnp.int32(0)),
                      lambda kind: jnp.float32(0.5))


def test_walk_gives_the_last_layer_s_counts_and_the_rows_of_both_segments():
    """Two expert segments of different kinds with a dense one between:
    the counts are the LAST expert layer's, `rows_held_all_layers` sums
    over BOTH segments' layers; a walk with no expert layer gives None."""
    segments = [("routed_a", 0, 2), ("dense", 2, 1), ("routed_b", 3, 2)]
    _, stats = _walk(segments)
    assert int(stats["rows_held"]) == 5 and int(stats["load_max"]) == 50
    assert int(stats["rows_held_all_layers"]) == 1 + 2 + 4 + 5
    assert _walk([("dense", 0, 3)])[1] is None


def test_walk_hands_a_tuple_carry_and_what_rides_beside_it_through():
    (x, seen), _ = _walk([("dense", 0, 2), ("routed", 2, 1)], x=1.0)
    assert int(seen) == 3
    assert float(x) == ((1.0 * 2 + 0.5) * 2 + 0.5) * 2 + 0.5


def test_one_kind_in_two_segments_is_traced_once():
    """`layer_fn` is one function object a (layer, kind, config): JAX finds
    the second segment's layer in its cache."""
    segments = [("routed", 0, 2), ("dense", 2, 1), ("routed", 3, 3)]
    stack.layer_fn.cache_clear()
    del TRACED[:]
    _, stats = jax.jit(lambda: _walk(segments))()
    assert int(stats["rows_held_all_layers"]) == 1 + 2 + 4 + 5 + 6
    assert sorted(TRACED) == ["dense", "routed"]
    c = Toy(segments=tuple(segments))
    assert stack.layer_fn(_toy_layer, "routed", c) \
        is stack.layer_fn(_toy_layer, "routed", c)


def _toy_shapes(kind, c):
    h = c.hidden
    return {"norm_w": ((h,), (None,), "ones"),
            "w": ((h, 3 * h) if kind == "wide" else (h, h),
                  ("embed", "mlp"), h),
            "b": ((h,), ("embed",), "small")}


_TOY = stack.Params(
    lambda c: list(c.segments), _toy_shapes,
    lambda c: {"embed": ((c.vocab, c.hidden), ("vocab", "embed"), c.hidden),
               "norm_w": ((c.hidden,), (None,), "ones")},
    {"small": lambda key, shape: 0.1 * jax.random.normal(key, shape)})


def test_a_pattern_of_two_kinds_stacks_under_its_positions_by_layer_index():
    """models/hybrid.py's order: the layer at position `pos` of repetition
    `rep` is layer first + rep x len(pattern) + pos, drawn from
    fold_in(key, that), one split a leaf in the table's order."""
    c = Toy(segments=((("narrow",), 0, 1), (("narrow", "wide"), 1, 3)))
    key = jax.random.key(7)
    params = _TOY.init(c, {"layers": key, "embed": jax.random.key(8)})
    np.testing.assert_array_equal(
        params["embed"], jax.random.normal(jax.random.key(8), (11, 4)) * 0.5)
    np.testing.assert_array_equal(params["norm_w"], np.ones(4))
    layers = params["layers"]
    assert sorted(layers) == ["seg00", "seg01"]
    assert sorted(layers["seg01"]) == ["0", "1"]
    assert layers["seg01"]["1"]["w"].shape == (3, 4, 12)
    for pos in (0, 1):
        for rep in range(3):
            keys = jax.random.split(
                jax.random.fold_in(key, 1 + rep * 2 + pos), 3)
            got = layers["seg01"][str(pos)]
            shape = got["w"].shape[1:]
            np.testing.assert_array_equal(
                got["w"][rep], jax.random.normal(keys[1], shape) * 0.5)
            np.testing.assert_array_equal(
                got["b"][rep], 0.1 * jax.random.normal(keys[2], (4,)))
            np.testing.assert_array_equal(got["norm_w"][rep], np.ones(4))
    # the tree's three uses
    axes = _TOY.logical_axes(c)
    assert axes["embed"] == ("vocab", "embed")
    assert axes["layers"]["seg01"]["1"]["w"] == ("layers", "embed", "mlp")
    assert jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple)) \
        == jax.tree.structure(params)
    assert _TOY.num_params(c) == sum(a.size for a in jax.tree.leaves(params))
    flags = _TOY.tree(c, lambda name, spec: name == "b")
    assert flags["layers"]["seg00"]["0"] == {"norm_w": False, "w": False,
                                             "b": True}
    assert flags["embed"] is False


@pytest.mark.parametrize("head", ["tok_embed", "lm_head"])
def test_the_tail_scores_against_the_head_the_model_names(head):
    """`token_nll` is `logits_nll` of the plain head's logits, tied or
    untied; the loss is its mean over the mask; the counts get `moe_`."""
    c = Toy()
    keys = jax.random.split(jax.random.key(3), 3)
    params = {"tok_embed": jax.random.normal(keys[0], (c.vocab, c.hidden)),
              "lm_head": jax.random.normal(keys[1], (c.vocab, c.hidden))}
    tokens = jax.random.randint(keys[2], (2, 6), 0, c.vocab)

    def forward_hidden(params, tokens, config):
        return jnp.tanh(params["tok_embed"][tokens]), {
            "rows_held": jnp.int32(3)}

    tail = stack.LossTail(forward_hidden, head=head)
    hidden = jnp.tanh(params["tok_embed"][tokens[:, :-1]])
    want = common.logits_nll(
        jnp.einsum("bsh,vh->bsv", hidden, params[head]), tokens[:, 1:])
    batch = {"tokens": tokens}
    np.testing.assert_allclose(tail.token_nll(params, batch, c), want,
                               rtol=1e-6)
    np.testing.assert_allclose(
        tail.forward(params, tokens[:, :-1], c),
        jnp.einsum("bsh,vh->bsv", hidden, params[head]), rtol=1e-6)
    mask = jnp.zeros((2, 6)).at[:, 2:4].set(1.0)
    loss, metrics = tail.loss_and_metrics(params, {**batch, "mask": mask}, c)
    np.testing.assert_allclose(loss, jnp.mean(want[:, 1:3]), rtol=1e-6)
    assert {k: int(v) for k, v in metrics.items()} == {"moe_rows_held": 3}
    np.testing.assert_allclose(tail.loss_fn(params, batch, c),
                               jnp.mean(want), rtol=1e-6)


@pytest.mark.parametrize("d,r", [(8, 4), (16, 4), (16, 12), (8, 8)])
def test_rotary_first_puts_pair_i_at_i_and_i_plus_half_a_head(d, r):
    """Half a head (one transpose), a quarter and three quarters (four
    slices), the whole (nothing to do): rot_a[i] lands at i, rot_b[i] at
    d/2 + i, the rest keeps its order, in every head alike; the tables get
    cos 1 and sin 0 behind the rotary pairs."""
    heads = 3
    x = jnp.arange(2 * heads * d).reshape(2, heads * d)
    got = np.asarray(stack.rotary_first(x, heads, r)).reshape(2, heads, d)
    a, b = np.arange(r // 2), np.arange(r // 2, r)
    rest = np.arange(r, d)
    order = np.concatenate([a, rest[:len(rest) // 2], b,
                            rest[len(rest) // 2:]])
    np.testing.assert_array_equal(
        got, np.asarray(x).reshape(2, heads, d)[..., order])
    one = np.asarray(stack.rotary_first(jnp.arange(d), 1, r))
    np.testing.assert_array_equal(one, order)
    cos, sin = stack.kernel_tables(
        *stack.rope_tables(5, r, 10000.0), d)
    assert cos.shape == sin.shape == (5, d // 2)
    assert bool((cos[:, r // 2:] == 1).all() & (sin[:, r // 2:] == 0).all())
    np.testing.assert_allclose(cos[:, 0], np.cos(np.arange(5)), rtol=1e-6)
