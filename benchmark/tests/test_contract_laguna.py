"""`laguna-s-2.1-train-d5e8` against the row of the guide's catalog it was
drawn from (Laguna-S-2.1, poolside): every key of the catalog's `config`
stands in the file under the same name, at the top level and again in
`model`; what differs is exactly `reduced`; no width is cut; the published
counts and the 32-chip deployment stand beside the cut; every assumption
has its reason; the readers declare what BENCHMARK.json says."""

import json
import os

from benchmark import harness

ROOT = harness.ROOT
NAME = "laguna-s-2.1-train-d5e8"
CELL = "train-swa-moe-d5"
FULL, SLIDING = "full_attention", "sliding_attention"
# the catalog row's `config`, copied (the guide is not in the repo)
CATALOG = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_hidden_layers": 48,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head",
    "sliding_window": 512,
    "rope_parameters": {
        FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
               "original_max_position_embeddings": 8192, "beta_slow": 1,
               "beta_fast": 32, "attention_factor": 1.4852030263919618,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1}},
    "layer_types": [FULL] + [SLIDING, SLIDING, SLIDING, FULL] * 11
    + [SLIDING] * 3,
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "gating_types": ["per_head"] * 48,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48] + [72, 72, 72, 48] * 11 + [72] * 3,
    "moe_router_logit_softcapping": 0}
SOURCE = "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json"
REDUCED = {"num_hidden_layers", "num_experts", "vocab_size"}
WIDTH_WORDS = ("hidden_size", "intermediate", "latent", "state", "proj",
               "head", "expand", "window", "per_tok")


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
            "hidden_size": 3072, "head_dim": 128, "num_key_value_heads": 8,
            "sliding_window": 512, "intermediate_size": 12288,
            "moe_intermediate_size": 1024,
            "shared_expert_intermediate_size": 1024,
            "num_experts_per_tok": 10,
            "moe_routed_scaling_factor": 2.5}.items():
        assert model[key] == value, key
    assert model["rope_parameters"] == CATALOG["rope_parameters"]
    # one of 32 chips that share each layer; the vocabulary over 8 of them
    chips = published["chips_that_share_a_layer"]
    assert chips == 32 and "THIRTY-TWO" in doc["deployment_stands_for"]
    assert model["num_experts"] * chips == CATALOG["num_experts"]
    assert published["chips_that_share_the_vocabulary"] == 8
    assert model["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert model["num_experts"] >= 8                        # the floors
    assert model["vocab_size"] * 8 >= CATALOG["vocab_size"]
    # the router keeps its width and its experts a token
    assert model["router_width"] == 256 and model["first_held_expert"] == 0
    # the leading dense layer once + ONE WHOLE PERIOD of the pattern in its
    # published 3 : 1 ratio, four layers behind the dense one
    n = model["num_hidden_layers"]
    assert n - 1 >= 4 and model["mlp_only_layers"] == [0]
    assert model["layer_types"][:n] == [FULL, SLIDING, SLIDING, SLIDING, FULL]
    assert model["num_attention_heads_per_layer"][:n] == [48, 72, 72, 72, 48]


def test_the_program_reads_the_file_as_the_cell_runs_it():
    """`build_config` on the file's `model` group: five layers in three
    segments, 48 / 72 heads by kind, 8 of 256 experts, 811,017,216
    parameters (ISSUE 36's table)."""
    from benchmark import arith_swa_moe
    from benchmark.drivers import train_model
    from ray_tpu.models import swa_moe

    _, _, doc = _entry_and_doc()
    config = train_model.build_config(doc["program"], doc["model"],
                                      doc["train"])
    assert [(kind[:2], repeats) for kind, _, repeats
            in swa_moe.segments(config)] == [
        ((FULL, 48), 1), ((SLIDING, 72), 3), ((FULL, 48), 1)]
    assert config.experts_held == (0, 8) and config.router_width == 256
    assert config.fused_ce and config.remat_policy == "full"
    assert swa_moe.num_params(config) == 811_017_216 \
        == arith_swa_moe.param_count(doc["model"])
    assert "811,017,216" in doc["train_why"]


def test_every_assumption_has_its_reason_and_the_limits_their_readings():
    _, _, doc = _entry_and_doc()
    assumed = doc["assumed"]
    for key in ("router", "gate", "shared_expert", "no_qk_norm_no_sink",
                "no_auxiliary_loss", "weights", "sequence_length", "window"):
        assert len(assumed[key]) > 60, key
    assert "softmax" in assumed["router"]
    assert "sigmoid" in assumed["gate"]
    check = doc["reference_check"]
    assert check["probe"] == "routed_experts"
    for key in ("tolerance", "token_rms_tolerance", "probe_rel_tolerance",
                "grad_rel_tolerance", "grad_worst_rel_tolerance"):
        assert 0 < check[key] < 1
    why = check["tolerance_why"]
    for word in ("bfloat16 router", "window", "sliding table", "gate",
                 "2.5", "capacity"):
        assert word in why, word


def test_no_capacity_factor_and_both_kernels_are_required():
    _, _, doc = _entry_and_doc()
    assert "capacity" not in json.dumps(doc["model"]).lower()
    assert doc["must_take_pallas"] == ["flash_attention", "grouped_matmul"]
    assert doc["driver"] == "train_model"
    assert doc["reference"] == "laguna_swa_moe"
    tr = doc["train"]
    assert tr["reference_rows"] == tr["batch_rows"] == 1
    assert tr["sequence_length"] == 8192


def test_the_cell_and_its_readers_are_what_benchmark_json_says():
    bench, _, _ = _entry_and_doc()
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "fixed-batch", 1)
    # (membership, not "the last": the next cell is appended behind this one)
    tokens = {m["name"]: m for m in bench["end_to_end"]}["train_tokens_per_s"]
    assert CELL in tokens["workloads"]
    mine = [m for m in bench["per_layer"] if m["name"].endswith(".swamoe")]
    assert len(mine) == 13
    readers = harness.load_layer_metrics()
    for m in mine:
        r = readers[m["name"]]
        assert (r.UNIT, r.SOURCE, r.LAYER, r.MOVES, r.WORKLOADS) == (
            m["unit"], m["source"], m["layer"], m["moves"], m["workloads"])
        assert m["workloads"] == [CELL]
    resolved = harness.resolve_cell(bench, CELL)
    assert {m["name"] for m in resolved["per_layer"]} == {
        m["name"] for m in mine}
    # no cell that was there reads a new reader, and the new cell none of
    # theirs
    for w in (w for w in bench["workloads"] if w["name"] != CELL):
        names = {m["name"] for m in
                 harness.resolve_cell(bench, w["name"])["per_layer"]}
        assert not any(n.endswith(".swamoe") for n in names)


def test_readers_find_nothing_and_do_not_raise_without_their_sources():
    """No trace, no timeline, bare counters: every `.swamoe` reader gives
    None (what a checkout that lacks the spans gives), none raises."""
    bench, _, doc = _entry_and_doc()
    resolved = harness.resolve_cell(bench, CELL)
    counters = {"model": doc["model"], "train": doc["train"], "chips": 1,
                "tokens_per_step": 8192,
                "device": {"kind": "TPU v5 lite"}}
    out = harness.read_layer_metrics(resolved, [], None, counters)
    assert out == {}


def test_the_counts_come_from_the_recorded_step_nearest_the_trace(
        tmp_path, monkeypatch):
    import sys
    from types import SimpleNamespace

    from benchmark import swa_moe_lib

    def step(n, start, rows):
        return {"name": "train.step", "worker": "rank0", "start": start,
                "end": start + 0.1, "attributes": {
                    "step": n, "moe_rows_held": rows / 4,
                    "moe_rows_held_all_layers": rows, "moe_load_max": 3.0 * n,
                    "moe_load_mean": 1.5, "moe_rows_bound": 65536.0}}

    doc = {"spans": [
        {"name": "startup.process", "worker": "driver", "start": 1000.0,
         "end": 1000.5, "attributes": {}},
        step(1, 1100.0, 10_000.0), step(16, 1150.0, 12_000.0),
        step(32, 1162.0, 9_000.0)]}
    run_dir = tmp_path / "train" / CELL
    run_dir.mkdir(parents=True)
    (run_dir / "timeline.json").write_text(json.dumps(doc))
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(sys.modules["__main__"], "T_PROCESS_START", 1000.1,
                        raising=False)
    cell = {"cell": {"name": CELL}}
    trace = SimpleNamespace(t0_epoch=1152.0)
    _, _, conf = _entry_and_doc()
    counters = {"model": conf["model"], "tokens_per_step": 8192}
    # four expert layers among the five
    assert swa_moe_lib.rows_per_layer(cell, counters, trace) == 3_000.0
    assert swa_moe_lib.group_sizes(cell, counters, trace) == [375.0] * 8
    reader = harness.load_layer_metrics()["expert_load_max_over_mean.swamoe"]
    assert reader.read([], trace, counters, cell) == 32.0
    # no recorded count: the expectation under even routing, 10 x 8 / 256
    (run_dir / "timeline.json").write_text(
        json.dumps({"spans": doc["spans"][:1]}))
    assert swa_moe_lib.rows_per_layer(cell, counters, trace) == 0.3125 * 8192
