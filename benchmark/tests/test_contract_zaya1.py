"""`zaya1-8b-train-d4` against the row of the guide's catalog it was drawn
from (ZAYA1-8B, Zyphra): every key of the catalog's `config` stands in the
file under the same name, at the top level and again in `model`; what
differs is exactly `reduced`; no width is cut and every expert is held; the
published counts and the deployment stand beside the cut; every assumption
has its reason; the readers declare what BENCHMARK.json says.  (It does not
assert that its cell is the last one, nor that a cell's metrics are exactly
its twins: a later PR appends.)"""

import json
import os

from benchmark import harness

ROOT = harness.ROOT
NAME = "zaya1-8b-train-d4"
CELL = "train-cca-moe-d4"
# the catalog row's `config`, copied (the guide is not in the repo)
CATALOG = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048,
    "layer_types": ["hybrid"] * 40, "lm_head_bias": False,
    "max_position_embeddings": 131072, "model_type": "zaya",
    "moe_intermediate_size": 2048, "num_attention_heads": 8,
    "num_experts": 16, "num_experts_per_tok": 1, "num_hidden_layers": 40,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
    "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 262272}
SOURCE = "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json"
REDUCED = {"num_hidden_layers", "vocab_size"}
WIDTH_WORDS = ("hidden_size", "intermediate", "latent", "state", "proj",
               "head", "expand", "window", "per_tok", "cca_time")


def _entry_and_doc():
    bench = harness.load_benchmark()
    entry = {c["name"]: c for c in bench["configs"]}[NAME]
    with open(os.path.join(ROOT, entry["file"])) as f:
        return bench, entry, json.load(f)


def test_every_catalog_key_is_there_and_only_reduced_differs():
    _, entry, doc = _entry_and_doc()
    assert entry["source"] == doc["source"] == SOURCE
    for where in (doc, doc["model"]):
        assert set(CATALOG) <= set(where)
        changed = {k for k in CATALOG if where[k] != CATALOG[k]}
        assert changed == set(doc["reduced"]) == set(entry["reduced"]) \
            == REDUCED
    # the file's top-level keys equal `model`'s
    assert {k: doc[k] for k in CATALOG} == {k: doc["model"][k]
                                            for k in CATALOG}
    assert set(doc["model"]) - set(CATALOG) == {"router_width",
                                                "first_held_expert"}
    assert entry["reduced"] == doc["reduced"] == ["num_hidden_layers",
                                                  "vocab_size"]
    assert len(entry["why"]) <= 200


def test_no_width_is_cut_every_expert_is_held_and_the_floors_hold():
    _, _, doc = _entry_and_doc()
    for key in doc["reduced"]:
        assert not key.endswith(("_dim", "_rank"))
        assert not any(w in key for w in WIDTH_WORDS), key
    model, published = doc["model"], doc["published"]
    assert {k: published[k] for k in REDUCED} == {
        k: CATALOG[k] for k in REDUCED}
    # every published width, by name
    for key, value in {
            "hidden_size": 2048, "head_dim": 128, "num_attention_heads": 8,
            "num_key_value_heads": 2, "cca_time0": 2, "cca_time1": 2,
            "partial_rotary_factor": 0.5, "router_hidden_size": 256,
            "num_experts_per_tok": 1, "moe_intermediate_size": 2048,
            "rms_norm_eps": 1e-5, "tie_word_embeddings": True}.items():
        assert model[key] == value, key
    assert model["rope_parameters"]["hybrid"]["rope_theta"] == 5_000_000
    # ONE chip holds a layer whole: all sixteen experts, the router's
    # sixteen outputs; the vocabulary over eight chips
    assert published["chips_that_share_a_layer"] == 1
    assert model["num_experts"] == published["num_experts"] == 16 \
        == model["router_width"] and model["first_held_expert"] == 0
    assert "num_experts" not in doc["reduced"]
    assert published["chips_that_share_the_vocabulary"] == 8
    assert model["vocab_size"] * 8 == CATALOG["vocab_size"]
    # every layer is of the one kind: a period is a layer, the floor four
    assert model["num_hidden_layers"] == 4
    assert set(model["layer_types"]) == {"hybrid"}
    for word in ("ten pipeline stages", "WHOLE", "eight"):
        assert word in doc["deployment_stands_for"], word


def test_the_program_reads_the_file_as_the_cell_runs_it():
    """`build_config` on the file's `model` group: four layers in one
    segment, all sixteen experts, 897,477,704 parameters (ISSUE 44's count
    to the unit), by the program's and by the benchmark's arithmetic."""
    from benchmark import arith_cca
    from benchmark.drivers import train_model
    from ray_tpu.models import cca_moe

    _, _, doc = _entry_and_doc()
    config = train_model.build_config(doc["program"], doc["model"],
                                      doc["train"])
    assert cca_moe.segments(config) == [(cca_moe.HYBRID, 0, 4)]
    assert config.experts_held == (0, 16) and config.router_width == 16
    assert config.latents == (1024, 256) and config.rotary_width == 64
    assert config.rope_theta == 5e6 and config.tie_word_embeddings
    assert config.fused_ce and config.remat_policy == "full"
    assert cca_moe.num_params(config) == 897_477_704 \
        == arith_cca.param_count(doc["model"])
    assert "897,477,704" in doc["train_why"]
    # the rehearsal's sizes build too
    tiny = train_model.build_config(
        doc["program"], {**doc["model"], **doc["rehearse"]["model"]},
        doc["train"])
    assert tiny.latents == (256, 128) and tiny.experts_held == (0, 16)


def test_every_assumption_has_its_reason_and_the_limits_their_readings():
    _, _, doc = _entry_and_doc()
    assumed = doc["assumed"]
    for key in ("A1_residual_scaling", "A2_norms", "A3_shifted_value_half",
                "A4_convolutions", "A5_qk_mean", "A6_l2_norm_temperature",
                "A7_router", "weights", "sequence_length"):
        assert len(assumed[key]) > 60, key
    assert "PLAIN" in assumed["A2_norms"]
    assert "previous" in assumed["A3_shifted_value_half"].lower()
    assert "depthwise" in assumed["A4_convolutions"]
    assert "NOT renormalised" in assumed["A7_router"] \
        and "erf" in assumed["A7_router"]
    check = doc["reference_check"]
    assert check["probe"] == "cca_mix"
    for key in ("tolerance", "token_rms_tolerance", "probe_rel_tolerance",
                "grad_rel_tolerance", "grad_worst_rel_tolerance"):
        assert 0 < check[key] < 1
    why = check["tolerance_why"]
    for word in ("tap", "depthwise", "mean", "current token", "tau", "l2",
                 "rope", "carry", "renormalised", "residual", "bfloat16",
                 "router"):
        assert word in why, word
    for key in ("published", "deployment_stands_for", "train_why"):
        assert doc[key]


def test_the_two_kernels_are_required_and_the_rows_are_the_references():
    _, _, doc = _entry_and_doc()
    assert doc["must_take_pallas"] == ["flash_attention", "grouped_matmul"]
    assert doc["driver"] == "train_model"
    assert doc["reference"] == "zaya1_cca_moe"
    tr = doc["train"]
    assert tr["reference_rows"] == tr["batch_rows"] in (1, 2)
    assert tr["sequence_length"] == 8192 and tr["remat_policy"] == "full"


def test_the_cell_and_its_readers_are_what_benchmark_json_says():
    """(Membership only: a later cell is appended behind this one, and a
    later PR may give this cell readers of its own.)"""
    bench, _, _ = _entry_and_doc()
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "fixed-batch", 1)
    assert len(cell["why"]) <= 200
    tokens = {m["name"]: m for m in bench["end_to_end"]}["train_tokens_per_s"]
    assert CELL in tokens["workloads"]
    mine = [m for m in bench["per_layer"] if m["name"].endswith(".cca")]
    assert len(mine) >= 25
    assert {"cca_mix_ms.cca", "router_ms.cca"} <= {m["name"] for m in mine}
    readers = harness.load_layer_metrics()
    for m in mine:
        r = readers[m["name"]]
        assert (r.UNIT, r.SOURCE, r.LAYER, r.MOVES, r.WORKLOADS) == (
            m["unit"], m["source"], m["layer"], m["moves"], m["workloads"])
        assert CELL in m["workloads"]
    resolved = harness.resolve_cell(bench, CELL)
    assert {m["name"] for m in mine} <= {m["name"]
                                         for m in resolved["per_layer"]}
    # every roofline share of the cell carries its unit, and a layer's name
    # is one BENCHMARK.json already had
    layers = {m["layer"] for m in bench["per_layer"]
              if not m["name"].endswith(".cca")}
    for m in mine:
        assert m["layer"] in layers, m
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    # no cell that was there reads a `.cca` reader
    for w in (w for w in bench["workloads"] if w["name"] != CELL):
        names = {m["name"] for m in
                 harness.resolve_cell(bench, w["name"])["per_layer"]}
        assert not any(n.endswith(".cca") for n in names)


def test_readers_find_nothing_and_do_not_raise_without_their_sources():
    """No trace, no timeline, bare counters: every `.cca` reader gives None
    (what a checkout that lacks the spans gives) or what it can count, none
    raises."""
    bench, _, doc = _entry_and_doc()
    resolved = harness.resolve_cell(bench, CELL)
    counters = {"model": doc["model"], "train": doc["train"], "chips": 1,
                "tokens_per_step": 8192 * doc["train"]["batch_rows"],
                "device": {"kind": "TPU v5 lite"}}
    out = harness.read_layer_metrics(resolved, [], None, counters)
    assert out == {}
