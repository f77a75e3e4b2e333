"""`nemotron-3-nano-30b-a3b-train-d9e8` against the row of the guide's catalog
it was drawn from (NVIDIA-Nemotron-3-Nano-30B-A3B-BF16): every key of the
catalog's `config` stands in the file under the same name, at the top level
and again in `model`; what differs is exactly `reduced` (and the pattern
string, cut to the nine layers `num_hidden_layers` says); no width is cut;
the published counts and the deployment stand beside the cut; every
assumption has its reason; the readers declare what BENCHMARK.json says.
(It does not assert that its cell is the last one, nor that a cell's metrics
are exactly its twins: a later PR appends.)"""

import json
import os

from benchmark import harness

ROOT = harness.ROOT
NAME = "nemotron-3-nano-30b-a3b-train-d9e8"
CELL = "train-ssd-moe-d9"
# the catalog row's `config`, copied (the guide is not in the repo)
CATALOG = {
    "attention_bias": False,
    "chunk_size": 128,
    "conv_kernel": 4,
    "expand": 2,
    "head_dim": 128,
    "hidden_size": 2688,
    "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64,
    "mamba_hidden_act": "silu",
    "mamba_num_heads": 64,
    "mamba_proj_bias": False,
    "max_position_embeddings": 262144,
    "mlp_bias": False,
    "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712,
    "n_group": 1,
    "n_groups": 8,
    "n_routed_experts": 128,
    "n_shared_experts": 1,
    "norm_eps": 1e-05,
    "norm_topk_prob": True,
    "num_attention_heads": 32,
    "num_experts_per_tok": 6,
    "num_hidden_layers": 52,
    "num_key_value_heads": 2,
    "num_logits_to_keep": 1,
    "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True,
    "residual_in_fp32": False,
    "rope_theta": 10000,
    "routed_scaling_factor": 2.5,
    "sliding_window": None,
    "ssm_state_size": 128,
    "tie_word_embeddings": False,
    "time_step_floor": 0.0001,
    "time_step_max": 0.1,
    "time_step_min": 0.001,
    "topk_group": 1,
    "use_bias": False,
    "use_conv_bias": True,
    "use_mamba_kernels": True,
    "vocab_size": 131072
}
SOURCE = ("https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/"
          "blob/main/config.json")
REDUCED = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
PATTERN = "hybrid_override_pattern"
WIDTH_WORDS = ("hidden_size", "intermediate", "latent", "state", "proj",
               "head", "expand", "window", "per_tok", "conv", "chunk",
               "n_groups")


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
        assert changed - {PATTERN} == set(doc["reduced"]) \
            == set(entry["reduced"]) == REDUCED
        # the pattern is the model's first nine characters
        assert where[PATTERN] == CATALOG[PATTERN][:9] == "MEMEM*EME"
        assert len(where[PATTERN]) == where["num_hidden_layers"] == 9
    assert {k: doc[k] for k in CATALOG} == {k: doc["model"][k]
                                            for k in CATALOG}
    assert set(doc["model"]) - set(CATALOG) == {"router_width",
                                                "first_held_expert"}
    assert entry["reduced"] == doc["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert len(entry["why"]) <= 200


def test_no_width_is_cut_and_the_floors_hold():
    _, _, doc = _entry_and_doc()
    for key in doc["reduced"]:
        assert not key.endswith(("_dim", "_rank"))
        assert not any(w in key for w in WIDTH_WORDS), key
    model, published = doc["model"], doc["published"]
    assert {k: published[k] for k in REDUCED} == {
        k: CATALOG[k] for k in REDUCED}
    assert published[PATTERN] == CATALOG[PATTERN]
    assert [published[PATTERN].count(k) for k in "ME*"] == [23, 23, 6]
    # every published width, by name
    for key, value in {
            "hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
            "n_groups": 8, "ssm_state_size": 128, "conv_kernel": 4,
            "chunk_size": 128, "num_attention_heads": 32,
            "num_key_value_heads": 2, "head_dim": 128,
            "num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
            "moe_intermediate_size": 1856,
            "moe_shared_expert_intermediate_size": 3712,
            "mlp_hidden_act": "relu2", "layer_norm_epsilon": 1e-05,
            "tie_word_embeddings": False, "router_width": 128}.items():
        assert model[key] == value, key
    # the floors: at least four layers and every kind of layer, at least 8
    # routed experts, at least an eighth of the vocabulary
    assert set(model[PATTERN]) == set("ME*") and len(model[PATTERN]) >= 4
    assert model["n_routed_experts"] == 8 \
        and model["first_held_expert"] + 8 <= model["router_width"]
    assert model["vocab_size"] * 8 == published["vocab_size"]
    assert published["chips_that_share_a_layer"] * model[
        "n_routed_experts"] == published["n_routed_experts"]
    assert published["chips_that_share_the_vocabulary"] == 8


def test_the_file_says_what_it_stands_for_and_what_it_assumed():
    _, _, doc = _entry_and_doc()
    assert "layers 0-8" in doc["deployment_stands_for"]
    assumed = doc["assumed"]
    assert {k.split("_")[0] for k in assumed if k[0] == "B"} == {
        f"B{i}" for i in range(1, 10)}
    assert "sequence_length" in assumed
    assert all(len(v) > 80 for v in assumed.values())
    assert doc["must_take_pallas"] == ["flash_attention", "grouped_matmul",
                                       "ssd_scan"]
    check = doc["reference_check"]
    assert check["probe"] == "ssd_scan" and len(check["tolerance_why"]) > 500
    tr = doc["train"]
    assert tr["batch_rows"] == tr["reference_rows"] \
        and tr["sequence_length"] == 8192 and tr["fused_ce"] \
        and tr["remat_policy"] == "full"
    assert "666,963,456" in doc["train_why"]
    rehearse = {**doc["model"], **doc["rehearse"]["model"]}
    assert set(rehearse[PATTERN]) == set("ME*")
    assert rehearse["mamba_num_heads"] > rehearse["n_groups"] > 1
    assert rehearse["moe_intermediate_size"] % 128 == 64
    assert rehearse["n_routed_experts"] < rehearse["router_width"]


def test_the_cell_and_its_readers_are_what_benchmark_json_says():
    bench, _, _ = _entry_and_doc()
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "fixed-batch", 1)
    assert len(cell["why"]) <= 200
    rate = {m["name"]: m for m in bench["end_to_end"]}["train_tokens_per_s"]
    assert CELL in rate["workloads"]
    readers = harness.load_layer_metrics()
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert {"ssd_fwd_roofline.ssd", "ssd_share.ssd", "mixer_chain_ms.ssd",
            "train_mfu.ssd", "step_ms.ssd"} <= {m["name"] for m in mine}
    for m in mine:
        mod = readers[m["name"]]
        assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE, mod.WORKLOADS) \
            == (m["unit"], m["layer"], m["moves"], m["source"],
                m["workloads"]), m["name"]
        assert m["moves"] == "train_tokens_per_s"
    assert len(bench["per_layer"]) <= 128
