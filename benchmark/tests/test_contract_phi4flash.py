"""`phi4-mini-flash-train-d8` against the row of the guide's catalog it was
drawn from (Phi-4-mini-flash-reasoning, microsoft): every key of the
catalog's `config` stands in the file under the same name, at the top
level and again in `model`; what differs is exactly `reduced`; no width is
cut; the cut keeps every kind of layer; the readers declare what
BENCHMARK.json says."""

import json
import os

from benchmark import harness

ROOT = harness.ROOT
NAME = "phi4-mini-flash-train-d8"
# the catalog row's `config`, copied (the guide is not in the repo)
CATALOG = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
           "intermediate_size": 10240, "layer_norm_eps": 1e-05,
           "max_position_embeddings": 262144, "mb_per_layer": 2,
           "model_type": "phi4flash", "num_attention_heads": 40,
           "num_hidden_layers": 32, "num_key_value_heads": 20,
           "resid_pdrop": 0, "sliding_window": 512,
           "tie_word_embeddings": True, "mlp_bias": False,
           "lm_head_bias": False, "vocab_size": 200064}
SOURCE = ("https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/"
          "blob/main/config.json")
WIDTH_WORDS = ("hidden_size", "intermediate", "latent", "state", "proj",
               "head", "expand", "window")


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
            == {"num_hidden_layers", "vocab_size"}
    assert {k: doc[k] for k in CATALOG} == {k: doc["model"][k]
                                            for k in CATALOG}


def test_no_width_is_cut_and_the_cut_keeps_to_the_floors():
    _, _, doc = _entry_and_doc()
    for key in doc["reduced"]:
        assert not key.endswith(("_dim", "_rank"))
        assert not any(w in key for w in WIDTH_WORDS), key
    model = doc["model"]
    # an eighth of the vocabulary, the guide's floor
    assert model["vocab_size"] * 8 == CATALOG["vocab_size"] == 200064
    kinds = model["layer_kinds"]
    assert len(kinds) == model["num_hidden_layers"] == 8
    assert set(kinds) == {"mamba", "window", "full", "gmu", "cross"}
    assert doc["published"]["num_hidden_layers"] == 32
    assert doc["published"]["vocab_size"] == 200064
    # the state-space sizes are the family's convention and listed as such
    assert (model["mamba_expand"] * model["hidden_size"], model["mamba_d_state"],
            model["mamba_d_conv"], model["mamba_dt_rank"]) == (5120, 16, 4, 160)
    for point in ("mamba_sizes", "positions", "attention_biases",
                  "differential_attention", "layer_order", "gmu", "weights",
                  "sequence_length"):
        assert point in doc["assumed"], point
    assert "8" in doc["deployment_stands_for"] or "eight" in \
        doc["deployment_stands_for"]


def test_the_cell_and_its_readers():
    bench, _, doc = _entry_and_doc()
    cell = {w["name"]: w for w in bench["workloads"]}["train-hybrid-d8"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "fixed-batch", 1)
    r = harness.resolve_cell(bench, "train-hybrid-d8")
    assert {m["name"] for m in r["end_to_end"]} == {"train_tokens_per_s",
                                                    "setup_s"}
    readers = harness.load_layer_metrics()
    names = {m["name"] for m in r["per_layer"]}
    assert len(names) == 17 and all(n.endswith(".hybrid") for n in names)
    for n in names:
        assert readers[n].WORKLOADS == ["train-hybrid-d8"]
    # a cell that has no trace, timeline or counters: every reader gives
    # nothing and raises nothing (what a parent commit would see)
    for n in names:
        assert readers[n].read([], None, {}, r) is None, n
    assert doc["driver"] == "train_model"
    assert doc["train"]["batch_rows"] * doc["train"]["sequence_length"] == 8192
    assert doc["must_take_pallas"] == ["flash_attention", "selective_scan"]
    check = doc["reference_check"]
    assert 0 < check["token_rms_tolerance"] < 0.0453 and check["tolerance"] > 0
    # the recurrence alone: between the program's 0.00166 and the
    # bfloat16 state's 0.0135; the first step's gradient: between the
    # sound runs' and the deliberate breaks' readings (tolerance_why)
    assert check["probe"] == "recurrence"
    assert 0.00166 < check["probe_rel_tolerance"] < 0.0135
    assert 0.0437 < check["grad_rel_tolerance"] < 0.184
    assert 0.0643 < check["grad_worst_rel_tolerance"] < 0.2185


def test_driver_builds_the_programs_config_and_refuses_a_missing_program():
    import pytest

    from benchmark.drivers import train_model
    from ray_tpu.models import hybrid

    _, _, doc = _entry_and_doc()
    config = train_model.build_config(doc["program"], doc["model"],
                                      doc["train"])
    assert isinstance(config, hybrid.HybridConfig)
    assert (config.vocab_size, config.num_layers, config.d_inner,
            config.remat_policy, config.fused_ce) == (25008, 8, 5120,
                                                      "full", True)
    assert hybrid.num_params(config) == 915_311_616

    class Args:
        rehearse, seed, seconds, trace = True, 0, 1.0, 0

    missing = {"config": dict(doc, program={"module": "ray_tpu.models.nope",
                                            "config": "X"}),
               "mix": {}, "cell": {"chips": 1, "name": "x"}}
    with pytest.raises(harness.BenchFailure, match="has no ray_tpu.models"):
        train_model.run(missing, Args, 0.0)
