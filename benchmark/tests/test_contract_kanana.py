"""`kanana-2-30b-a3b-train-d6e16` against the row of the guide's catalog it
was drawn from (kanana-2-30b-a3b-instruct-2601, kakaocorp): every key of the
catalog's `config` stands in the file under the same name, at the top level
and again in `model`; what differs is exactly `reduced`; no width is cut;
the published counts and the eight-chip deployment stand beside the cut;
the readers declare what BENCHMARK.json says."""

import json
import os

from benchmark import harness

ROOT = harness.ROOT
NAME = "kanana-2-30b-a3b-train-d6e16"
CELL = "train-moe-mla-d6"
# the catalog row's `config`, copied (the guide is not in the repo)
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}
SOURCE = ("https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/"
          "blob/main/config.json")
REDUCED = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
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
    assert {k: doc[k] for k in CATALOG} == {k: doc["model"][k]
                                            for k in CATALOG}


def test_no_width_is_cut_and_the_cut_keeps_to_the_floors():
    _, _, doc = _entry_and_doc()
    for key in doc["reduced"]:
        assert not key.endswith(("_dim", "_rank"))
        assert not any(w in key for w in WIDTH_WORDS), key
    model, published = doc["model"], doc["published"]
    assert {k: published[k] for k in REDUCED} == {
        k: CATALOG[k] for k in REDUCED}
    chips = published["chips_that_share_a_layer"]
    assert chips == 8 and "eight" in doc["deployment_stands_for"]
    # the chip's share: an eighth of the experts and of the vocabulary
    assert model["n_routed_experts"] * chips == CATALOG["n_routed_experts"]
    assert model["vocab_size"] * chips == CATALOG["vocab_size"]
    assert model["n_routed_experts"] >= 8                   # the floor
    # the leading dense layer once + at least four of the layers behind it
    assert model["first_k_dense_replace"] == 1
    assert model["num_hidden_layers"] - 1 >= 4
    # the router keeps its width and its experts a token
    assert model["router_width"] == 128 and model["first_held_expert"] == 0
    assert model["num_experts_per_tok"] == 6


def test_no_capacity_factor_and_both_kernels_are_required():
    _, _, doc = _entry_and_doc()
    assert "capacity" not in json.dumps(doc["model"]).lower()
    assert doc["must_take_pallas"] == ["flash_attention", "grouped_matmul"]
    assert doc["driver"] == "train_model"
    assert doc["reference"] == "deepseek_v3_mla_moe"
    assert doc["reference_check"]["probe"] == "routed_experts"
    tr = doc["train"]
    assert tr["reference_rows"] == tr["batch_rows"]
    assert tr["sequence_length"] == 8192


def test_the_cell_and_its_readers_are_what_benchmark_json_says():
    bench, _, _ = _entry_and_doc()
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "fixed-batch", 1)
    tokens = {m["name"]: m for m in bench["end_to_end"]}["train_tokens_per_s"]
    assert tokens["workloads"][-1] == CELL
    mine = [m for m in bench["per_layer"] if m["name"].endswith(".moe")]
    assert len(mine) == 11
    readers = harness.load_layer_metrics()
    for m in mine:
        r = readers[m["name"]]
        assert (r.UNIT, r.SOURCE, r.LAYER, r.MOVES, r.WORKLOADS) == (
            m["unit"], m["source"], m["layer"], m["moves"], m["workloads"])
        assert m["workloads"] == [CELL]
    resolved = harness.resolve_cell(bench, CELL)
    assert {m["name"] for m in resolved["per_layer"]} == {
        m["name"] for m in mine}


def test_readers_find_nothing_and_do_not_raise_without_their_sources():
    """No trace, no timeline, bare counters: every `.moe` reader gives
    None (what a checkout that lacks the spans gives), none raises."""
    bench, _, doc = _entry_and_doc()
    resolved = harness.resolve_cell(bench, CELL)
    counters = {"model": doc["model"], "train": doc["train"], "chips": 1,
                "tokens_per_step": 16384,
                "device": {"kind": "TPU v5 lite"}}
    out = harness.read_layer_metrics(resolved, [], None, counters)
    assert out == {}


def test_the_counts_come_from_the_recorded_step_nearest_the_trace(
        tmp_path, monkeypatch):
    """timeline.json holds the `train.step` spans of steps 1, 2, 4, ...
    with the routing counts; a reader takes those nearest the traced
    window's start, and the first with no trace."""
    import sys
    from types import SimpleNamespace

    from benchmark import moe_lib

    def step(n, start, rows):
        return {"name": "train.step", "worker": "rank0", "start": start,
                "end": start + 0.1, "attributes": {
                    "step": n, "moe_rows_held": rows / 5,
                    "moe_rows_held_all_layers": rows, "moe_load_max": 3.0 * n,
                    "moe_load_mean": 1.5, "moe_rows_bound": 98304.0}}

    doc = {"spans": [
        {"name": "startup.process", "worker": "driver", "start": 1000.0,
         "end": 1000.5, "attributes": {}},
        step(1, 1100.0, 50_000.0), step(2, 1130.0, 60_000.0),
        step(16, 1150.0, 70_000.0), step(32, 1162.0, 40_000.0),
        {"name": "train.step", "worker": "rank0", "start": 1151.0,
         "end": 1151.1, "attributes": {"step": 17}}]}
    run_dir = tmp_path / "train" / CELL
    run_dir.mkdir(parents=True)
    (run_dir / "timeline.json").write_text(json.dumps(doc))
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(sys.modules["__main__"], "T_PROCESS_START", 1000.1,
                        raising=False)
    cell = {"cell": {"name": CELL}}
    assert moe_lib.step_counts(cell)["step"] == 1
    trace = SimpleNamespace(t0_epoch=1152.0)
    assert moe_lib.step_counts(cell, trace)["step"] == 16
    _, _, conf = _entry_and_doc()
    counters = {"model": conf["model"], "tokens_per_step": 16384}
    assert moe_lib.rows_per_layer(cell, counters, trace) == 14_000.0
    assert moe_lib.group_sizes(cell, counters, trace) == [875.0] * 16
    reader = harness.load_layer_metrics()["expert_load_max_over_mean.moe"]
    assert reader.read([], trace, counters, cell) == 32.0
    # no recorded count: the expectation under even routing
    (run_dir / "timeline.json").write_text(json.dumps({"spans": doc["spans"][:1]}))
    assert moe_lib.step_counts(cell, trace) == {}
    assert moe_lib.rows_per_layer(cell, counters, trace) == 0.75 * 16384
