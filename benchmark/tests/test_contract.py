"""BENCHMARK.json against the contract's limits and against the files it
names; the generators' steadiness rule."""

import json
import os
import re

import numpy as np

from benchmark import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _bench():
    return harness.load_benchmark()


def test_shape_and_names():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmark"] and 1 <= b["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in b[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    metrics = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert UNIT.match(m["unit"])
    for w in b["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(b["workloads"]) // 4)
    assert len(json.dumps(b)) < 64 * 1024


def test_every_cell_reports_what_it_must():
    b = _bench()
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for w in b["workloads"]:
        r = harness.resolve_cell(b, w["name"])
        names = {m["name"] for m in r["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert r["per_layer"], w["name"]
        for m in r["per_layer"]:
            assert m["moves"] in names, (w["name"], m["name"])
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_readers_declare_what_benchmark_json_says():
    b = _bench()
    readers = harness.load_layer_metrics()
    entries = {m["name"]: m for m in b["per_layer"]}
    assert set(entries) == set(readers)
    for name, e in entries.items():
        mod = readers[name]
        assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
            e["unit"], e["layer"], e["moves"], e["source"]), name
        assert mod.WORKLOADS == e.get("workloads"), name


def test_configs_keep_every_width():
    b = _bench()
    files = {c["name"]: c for c in b["configs"]}
    base = json.load(open(os.path.join(
        ROOT, files["smollm2-1.7b-train-fsdp4"]["file"])))["model"]
    # SmolLM2-1.7B as published (ISSUE 26); fsdp4 reduces nothing
    published = {"hidden_size": 2048, "intermediate_size": 8192,
                 "num_hidden_layers": 24, "num_attention_heads": 32,
                 "num_key_value_heads": 32, "head_dim": 64,
                 "vocab_size": 49152}
    assert {k: base[k] for k in published} == published
    for c in b["configs"]:
        doc = json.load(open(os.path.join(ROOT, c["file"])))
        assert doc["reduced"] == c["reduced"]
        changed = {k for k in base if doc["model"][k] != base[k]}
        assert changed == set(c["reduced"])
        for k in c["reduced"]:
            assert "size" not in k and not k.endswith(("_dim", "_rank"))


def test_same_seed_same_batch_other_seed_same_shape():
    """Every mix names a generator that is there; the same seed gives the
    same inputs, another seed (a large one, as the driver's are) the same
    amount of work in other tokens."""
    b = _bench()
    for w in b["workloads"]:
        r = harness.resolve_cell(b, w["name"])
        gen = harness.load_generator(r["mix"]["generator"])
        feeds = []
        for seed in (7, 7, 2 ** 31 + 11):
            plan = gen.plan(r["mix"], seed, 20.0)
            assert json.loads(json.dumps(plan)) == plan  # crosses processes
            feed = gen.batches(plan, 3, 16, 49152)
            feeds.append([next(feed), next(feed)])
        (a1, a2), (b1, _), (c1, _) = feeds
        assert a1.shape == c1.shape == (3, 17) and a1.dtype == np.int32
        assert a1.min() >= 0 and a1.max() < 49152
        assert np.array_equal(a1, b1) and not np.array_equal(a1, c1)
        assert a2 is a1     # fixed_batch: one transfer to the device


def test_a_mix_that_names_no_generator_file_fails():
    import pytest

    with pytest.raises(harness.BenchFailure):
        harness.load_generator("no_such_generator")
