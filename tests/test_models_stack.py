"""models/stack.py: the layer stack the five segmented model files share.

The first half is the net under the move (PR 47), written on PR 46's tree
before any model file was touched: what `init_params`, `logical_axes`,
`not_trained` and `num_params` gave THEN, as digests.  A parameter that is
drawn from another key, stacked in another order, named, shaped or sharded
otherwise moves a digest; a change that means to replaces it here and says
so.  (The compiled step programs have theirs in tests/test_tpu_aot_compile
.py's `PARENT_HLO_SHA256` and tests/test_tpu_aot_compile_cca.py.)
"""

import hashlib
import importlib
import json
import os

import jax
import numpy as np
import pytest

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
