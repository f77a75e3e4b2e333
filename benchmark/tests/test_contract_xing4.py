"""`xing4.0-29b-a4b-train-d5e8` against the row of the guide's catalog it was
drawn from (Xing4.0-29B-A4B, XingChen-AGI): every key of the catalog's
`config` stands in the file under the same name, at the top level and again
in `model`; what differs is exactly `reduced`; no width is cut; the published
counts and the eight-chip deployment stand beside the cut; the readers
declare what BENCHMARK.json says, and the counters' readers take the first
recorded step's and the one nearest the trace."""

import json
import os

from benchmark import harness
from cell_contract import EVERY_CELL, ROUTED, check

ROOT = harness.ROOT
NAME = "xing4.0-29b-a4b-train-d5e8"
CELL = "train-mhc-mla-moe-d5"
# the catalog row's `config`, copied (the guide is not in the repo)
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 131072}
SOURCE = ("https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/"
          "config.json")
# the leading dense layers count once (the guide's section 4): two -> one
# .. and the prediction block lies on the last stage's chip: ISSUE 52's one
# rule for a row of 8192 that does not fit beside it
REDUCED = {"num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size", "num_nextn_predict_layers"}
WIDTH_WORDS = ("hidden_size", "intermediate", "latent", "state", "proj",
               "head", "expand", "window", "per_tok")

# beside what every cell reports (cell_contract.py)
NEW = {"hc_fwd_roofline", "hc_share", "residual_mix_ms", "hc_comb_row_err"}
MUST_REPORT = EVERY_CELL | ROUTED | {"mla_fwd_roofline"} | NEW
FAMILY = "xing4_hc_mla_moe"
FACES = {"mla_forward", "grouped_forward", "grouped_all", "hc_forward",
         "hc_all"}


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
    assert chips == 8 and "EIGHT" in doc["deployment_stands_for"]
    # the chip's share: an eighth of the experts and of the vocabulary
    assert model["n_routed_experts"] * chips == CATALOG["n_routed_experts"]
    assert model["vocab_size"] * chips == CATALOG["vocab_size"]
    assert model["n_routed_experts"] >= 8                   # the floor
    # the leading dense layers once + at least four of the layers behind
    assert model["first_k_dense_replace"] == 1
    assert model["num_hidden_layers"] - 1 >= 4
    # the router keeps its width and its experts a token; the lanes, their
    # rounds are the published ones; the prediction block is off this chip
    assert model["router_width"] == 64 and model["first_held_expert"] == 0
    assert (model["num_experts_per_tok"], model["hc_mult"],
            model["hc_sinkhorn_iters"], model["num_nextn_predict_layers"]) \
        == (4, 4, 20, 0)
    assert doc["published"]["num_nextn_predict_layers"] == 1
    assert "mtp_loss" in doc["assumed"]


def test_no_capacity_factor_and_every_kernel_is_required():
    _, _, doc = _entry_and_doc()
    assert "capacity" not in json.dumps(doc["model"]).lower()
    assert doc["must_take_pallas"] == ["flash_attention", "grouped_matmul",
                                       "hyper_connection"]
    assert doc["driver"] == "train_model"
    assert doc["reference"] == FAMILY
    assert doc["program"] == {"module": "ray_tpu.models.latent_moe",
                              "config": "LatentMoEConfig"}
    assert doc["reference_check"]["probe"] == "residual_mix"
    tr = doc["train"]
    assert tr["reference_rows"] == tr["batch_rows"] == 1
    assert tr["sequence_length"] == 8192
    for key in ("lanes", "attention", "experts", "mtp_loss", "weights",
                "sequence_length"):
        assert len(doc["assumed"][key]) > 40, key


def test_the_program_builds_the_configuration():
    """The model group's keys that are fields of the dataclass build it: the
    lanes, the query latent and yarn are ON, the block off this chip."""
    from benchmark.drivers import train_model

    _, _, doc = _entry_and_doc()
    c = train_model.build_config(doc["program"], doc["model"], doc["train"])
    assert (c.hc_mult, c.q_lora_rank, c.num_nextn_predict_layers,
            c.n_shared_experts, c.experts_held) == (4, 768, 0, 1, (0, 8))
    assert c.yarn["factor"] == 64 and abs(c.softmax_scale * 192 ** 0.5
                                          - 2.00474) < 1e-4


def test_the_cell_and_its_readers_are_what_benchmark_json_says():
    check(CELL, NAME, MUST_REPORT, FAMILY, FACES)


def test_readers_find_nothing_and_do_not_raise_without_their_sources():
    """No trace, no timeline, bare counters: every reader of the cell gives
    None (what a checkout that lacks the spans gives), none raises."""
    bench, _, doc = _entry_and_doc()
    resolved = harness.resolve_cell(bench, CELL)
    counters = {"model": doc["model"], "train": doc["train"], "chips": 1,
                "tokens_per_step": 8192, "device": {"kind": "TPU v5 lite"}}
    assert harness.read_layer_metrics(resolved, [], None, counters) == {}


def test_the_counter_comes_from_the_recorded_steps(tmp_path, monkeypatch):
    """`hc_comb_row_err` is the FIRST recorded step's; a timeline without
    it gives nothing."""
    import sys
    from types import SimpleNamespace

    def step(n, start, **attributes):
        return {"name": "train.step", "worker": "rank0", "start": start,
                "end": start + 0.1, "attributes": {"step": n, **attributes}}

    doc = {"spans": [
        {"name": "startup.process", "worker": "driver", "start": 1000.0,
         "end": 1000.5, "attributes": {}},
        step(1, 1100.0, hc_row_err=0.01, moe_rows_held=9.0),
        step(16, 1150.0, hc_row_err=0.02),
        step(17, 1151.0)]}
    run_dir = tmp_path / "train" / CELL
    run_dir.mkdir(parents=True)
    (run_dir / "timeline.json").write_text(json.dumps(doc))
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(sys.modules["__main__"], "T_PROCESS_START", 1000.1,
                        raising=False)
    cell = {"cell": {"name": CELL}, "config": {"reference": FAMILY}}
    readers = harness.load_layer_metrics()
    trace = SimpleNamespace(t0_epoch=1152.0)
    assert readers["hc_comb_row_err"].read([], trace, {}, cell) == 0.01
    assert readers["hc_comb_row_err"].read([], None, {}, cell) == 0.01
    (run_dir / "timeline.json").write_text(
        json.dumps({"spans": doc["spans"][:1] + doc["spans"][-1:]}))
    assert readers["hc_comb_row_err"].read([], trace, {}, cell) is None
