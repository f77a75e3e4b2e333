"""`qwen3-next-80b-a3b-train-d4e32` against the row of the guide's catalog it
was drawn from (Qwen3-Next-80B-A3B-Instruct, Qwen): every key of the
catalog's `config` stands in the file under the same name, at the top level
and again in `model`; what differs is exactly `reduced`; no width is cut; the
published counts and the 16-chip deployment stand beside the cut; every
assumption has its reason; the readers declare what BENCHMARK.json says."""

import json
import os

from benchmark import harness

ROOT = harness.ROOT
NAME = "qwen3-next-80b-a3b-train-d4e32"
CELL = "train-gdn-moe-d4"
# the catalog row's `config`, copied (the guide is not in the repo)
CATALOG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
SOURCE = ("https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/"
          "config.json")
REDUCED = {"num_hidden_layers", "num_experts", "vocab_size"}
WIDTH_WORDS = ("hidden_size", "intermediate", "latent", "state", "proj",
               "head", "expand", "window", "per_tok", "conv")


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
    assert entry["reduced"] == doc["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert len(entry["why"]) <= 200


def test_no_width_is_cut_and_the_cut_keeps_to_the_floors():
    _, _, doc = _entry_and_doc()
    for key in doc["reduced"]:
        assert not key.endswith(("_dim", "_rank"))
        assert not any(w in key for w in WIDTH_WORDS), key
    model, published = doc["model"], doc["published"]
    assert {k: published[k] for k in REDUCED} == {
        k: CATALOG[k] for k in REDUCED}
    # every published width, by name
    for key, value in {
            "hidden_size": 2048, "head_dim": 256, "num_attention_heads": 16,
            "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
            "linear_num_key_heads": 16, "linear_num_value_heads": 32,
            "linear_key_head_dim": 128, "linear_value_head_dim": 128,
            "linear_conv_kernel_dim": 4, "moe_intermediate_size": 512,
            "shared_expert_intermediate_size": 512,
            "num_experts_per_tok": 10, "intermediate_size": 5120}.items():
        assert model[key] == value, key
    # one of 16 chips that share each layer; the vocabulary over 8 of them
    chips = published["chips_that_share_a_layer"]
    assert chips == 16 and "SIXTEEN" in doc["deployment_stands_for"]
    assert model["num_experts"] * chips == CATALOG["num_experts"]
    assert published["chips_that_share_the_vocabulary"] == 8
    assert model["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert model["num_experts"] >= 8                        # the floors
    # the router keeps its width and its experts a token
    assert model["router_width"] == 512 and model["first_held_expert"] == 0
    # ONE WHOLE PERIOD of the pattern in its published 3 : 1 ratio, the
    # floor of four layers; no leading dense layer
    n, every = model["num_hidden_layers"], model["full_attention_interval"]
    assert n == every == 4 and model["mlp_only_layers"] == []


def test_the_program_reads_the_file_as_the_cell_runs_it():
    """`build_config` on the file's `model` group: four layers in two
    segments, 32 of 512 experts, 625,667,136 parameters (ISSUE 42's
    count), by the program's and by the benchmark's arithmetic."""
    from benchmark import arith_gdn
    from benchmark.drivers import train_model
    from ray_tpu.models import gdn_moe

    _, _, doc = _entry_and_doc()
    config = train_model.build_config(doc["program"], doc["model"],
                                      doc["train"])
    assert gdn_moe.segments(config) == [(gdn_moe.LINEAR, 0, 3),
                                        (gdn_moe.FULL, 3, 1)]
    assert config.experts_held == (0, 32) and config.router_width == 512
    assert config.rotary_width == 64 and config.conv_channels == 8192
    assert config.fused_ce and config.remat_policy == "full"
    assert gdn_moe.num_params(config) == 625_667_136 \
        == arith_gdn.param_count(doc["model"])
    assert "625,667,136" in doc["train_why"]
    # the rehearsal's sizes build too
    tiny = train_model.build_config(
        doc["program"], {**doc["model"], **doc["rehearse"]["model"]},
        doc["train"])
    assert gdn_moe.segments(tiny) == [(gdn_moe.LINEAR, 0, 3),
                                      (gdn_moe.FULL, 3, 1)]


def test_every_assumption_has_its_reason_and_the_limits_their_readings():
    _, _, doc = _entry_and_doc()
    assumed = doc["assumed"]
    for key in ("norms", "linear_mixer", "full_layer", "experts", "no_mtp",
                "weights", "sequence_length"):
        assert len(assumed[key]) > 60, key
    assert "ZERO-CENTRED" in assumed["norms"]
    assert "l2-normalised" in assumed["linear_mixer"] \
        and "FROM ZERO" in assumed["linear_mixer"] \
        and "WITHOUT bias" in assumed["linear_mixer"]
    assert "multi-token-prediction" in assumed["no_mtp"]
    check = doc["reference_check"]
    assert check["probe"] == "gated_delta_rule"
    for key in ("tolerance", "token_rms_tolerance", "probe_rel_tolerance",
                "grad_rel_tolerance", "grad_worst_rel_tolerance"):
        assert 0 < check[key] < 1
    why = check["tolerance_why"]
    for word in ("decay", "beta", "l2", "bfloat16", "conv", "rope", "gate",
                 "shared expert", "router"):
        assert word in why, word
    for key in ("published", "deployment_stands_for", "train_why"):
        assert doc[key]


def test_the_three_kernels_are_required_and_the_rows_are_the_references():
    _, _, doc = _entry_and_doc()
    assert doc["must_take_pallas"] == ["flash_attention", "grouped_matmul",
                                       "gated_delta_rule"]
    assert doc["driver"] == "train_model"
    assert doc["reference"] == "qwen3_next_gdn_moe"
    tr = doc["train"]
    assert tr["reference_rows"] == tr["batch_rows"] in (1, 2, 3, 4)
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
    mine = [m for m in bench["per_layer"] if m["name"].endswith(".gdn")]
    assert len(mine) >= 26
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
              if not m["name"].endswith(".gdn")}
    for m in mine:
        assert m["layer"] in layers, m
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    # no cell that was there reads a `.gdn` reader
    for w in (w for w in bench["workloads"] if w["name"] != CELL):
        names = {m["name"] for m in
                 harness.resolve_cell(bench, w["name"])["per_layer"]}
        assert not any(n.endswith(".gdn") for n in names)


def test_readers_find_nothing_and_do_not_raise_without_their_sources():
    """No trace, no timeline, bare counters: every `.gdn` reader gives None
    (what a checkout that lacks the spans gives) or what it can count, none
    raises."""
    bench, _, doc = _entry_and_doc()
    resolved = harness.resolve_cell(bench, CELL)
    counters = {"model": doc["model"], "train": doc["train"], "chips": 1,
                "tokens_per_step": 8192 * doc["train"]["batch_rows"],
                "device": {"kind": "TPU v5 lite"}}
    out = harness.read_layer_metrics(resolved, [], None, counters)
    assert out == {}
