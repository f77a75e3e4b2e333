"""A later PR adds a configuration, a traffic generator, a mix, a layer
metric and a cell as NEW files plus entries in BENCHMARK.json, and edits
no file that is there.  Shown on a temporary copy, rehearsed on the CPU at tiny size
(kernels interpreted, no device metric, never `correct: true`).  Also:
with no program beside it the benchmark exits non-zero and prints no
result."""

import json
import os
import shutil
import subprocess
import sys

from benchmark import harness

ROOT = harness.ROOT


def _copy(tmp_path, with_program=True):
    dst = tmp_path / "checkout"
    dst.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst / "BENCHMARK.json")
    shutil.copytree(os.path.join(ROOT, "benchmark"), dst / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "_probe.py"))
    if with_program:
        os.symlink(os.path.join(ROOT, "ray_tpu"), dst / "ray_tpu")
    return dst


def _digest(folder):
    out = {}
    for base, _, files in os.walk(folder):
        if "__pycache__" in base:
            continue
        for fn in files:
            p = os.path.join(base, fn)
            out[os.path.relpath(p, folder)] = open(p, "rb").read()
    return out


def _run(dst, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_COMPILATION_CACHE_DIR"] = str(dst / ".jax_cache")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", *args], cwd=dst, env=env,
        capture_output=True, text=True, timeout=600)


def test_new_files_are_found_and_run(tmp_path):
    dst = _copy(tmp_path)
    before = _digest(dst / "benchmark")
    cfg = json.load(open(dst / "benchmark/configs/smollm2-1.7b-train-d12.json"))
    cfg = {**cfg, "name": "tiny-new"}
    cfg["model"] = {**cfg["model"], **cfg["rehearse"]["model"]}
    json.dump(cfg, open(dst / "benchmark/configs/tiny-new.json", "w"))
    # a generator of a NEW kind: two batches in turn, so every step moves
    # one to the device (fixed_batch moves one in all)
    (dst / "benchmark/generators/two_batches.py").write_text(
        "import numpy as np\n\n\n"
        "def plan(mix, seed, seconds):\n"
        '    return {"seed": int(seed), "warmup_steps": mix["warmup_steps"],\n'
        '            "steps_per_sync": mix["steps_per_sync"]}\n\n\n'
        "def batches(plan, rows, seq, vocab):\n"
        '    rng = np.random.default_rng(plan["seed"] % (2 ** 31 - 1))\n'
        "    pair = [rng.integers(0, vocab, (rows, seq + 1)).astype(np.int32)\n"
        "            for _ in range(2)]\n"
        "    while True:\n"
        "        yield from pair\n")
    json.dump({"generator": "two_batches", "warmup_steps": 1,
               "steps_per_sync": 2},
              open(dst / "benchmark/traffic/two-batches-sync2.json", "w"))
    (dst / "benchmark/layer_metrics/batches_moved.new.py").write_text(
        'NAME, UNIT, SOURCE = "batches_moved.new", "count", "program_counter"\n'
        'LAYER, MOVES, WORKLOADS = "trainer", "train_tokens_per_s", ["new-cell"]\n'
        "\n\ndef read(spans, trace, counters, cell):\n"
        '    return float(counters["batches_moved"])\n')
    bench = json.load(open(dst / "BENCHMARK.json"))
    bench["configs"].append({
        "name": "tiny-new", "source": cfg["source"],
        "file": "benchmark/configs/tiny-new.json",
        "reduced": ["num_hidden_layers"], "why": "test"})
    bench["workloads"].append({
        "name": "new-cell", "config": "tiny-new",
        "traffic": "two-batches-sync2", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("new-cell")
    bench["per_layer"].append({
        "name": "batches_moved.new", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "trainer",
        "moves": "train_tokens_per_s", "workloads": ["new-cell"]})
    json.dump(bench, open(dst / "BENCHMARK.json", "w"))

    r = _run(dst, "--workload", "new-cell", "--seed", str(2 ** 31 + 3),
             "--seconds", "2", "--trace", "1", "--rehearse")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = [json.loads(l) for l in r.stdout.splitlines()
             if l.startswith("{")]
    last = lines[-1]
    assert last["correct"] is False and last["metrics"] == {}
    assert last["device"]["platform"] == "cpu"
    rehearsal = [l for l in lines if l.get("phase") == "rehearsal"][-1]
    assert rehearsal["passed"] is True
    # the new generator fed the old driver: a batch moved at every step
    train = [l for l in lines if l.get("phase") == "train"][-1]
    assert rehearsal["metrics"]["batches_moved.new"] == train["steps"] + 2
    assert train["steps"] >= 2
    after = _digest(dst / "benchmark")
    assert {k: v for k, v in after.items() if k in before} == before


def test_without_the_program_it_fails_and_prints_no_result(tmp_path):
    dst = _copy(tmp_path, with_program=False)
    r = _run(dst, "--workload", "train-d12", "--seed", "1", "--seconds",
             "2", "--trace", "0")
    assert r.returncode != 0
    assert not any(l.startswith("{") and '"correct"' in l
                   for l in r.stdout.splitlines())
