"""The thirteen readers PR 39 added (`part_ms.*`, `bare_copy_ms`,
`step_program_hbm_share`) and `benchmark/part_lib.py`, on what a traced run
of `train-swa-moe-d5` on the chip left behind (recorded, PR 39, one file):
`trace`, ONE step of device 0's "XLA Modules" and "XLA Ops" lines as
`TraceView.to_json` writes them, and `timeline`, the run's timeline.json
cut to its `startup.process` span and its `programs` (the step program's
report: 3,449 instruction rows and `memory_analysis`)."""

import gzip
import json
import os
import sys

import pytest

from benchmark import harness, part_lib, timeline_lib
from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "part_swa_moe_d5_v5e.json.gz")
CELL = {"cell": {"name": "train-swa-moe-d5"}}
NEW = ("part_ms.attention_kernels", "part_ms.attention_proj",
       "part_ms.attention_glue", "part_ms.mlp", "part_ms.scan",
       "part_ms.routed_kernels", "part_ms.routed_xla", "part_ms.loss",
       "part_ms.optimizer", "part_ms.unscoped", "part_ms.idle_in_program",
       "bare_copy_ms", "step_program_hbm_share")


def _recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def _timeline():
    return _recorded()["timeline"]


def _trace():
    return tr.TraceView({
        plane: {line: [tuple(e) for e in evs] for line, evs in lines.items()}
        for plane, lines in _recorded()["trace"]["planes"].items()})


@pytest.fixture
def recorded_run(tmp_path, monkeypatch):
    """The recorded timeline where the driver would have put it, in a
    process that began just after the recorded run's did; -> the trace."""
    doc = _timeline()
    run_dir = tmp_path / "train" / CELL["cell"]["name"]
    run_dir.mkdir(parents=True)
    with open(run_dir / "timeline.json", "w") as f:
        json.dump(doc, f)
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    began = timeline_lib.spans(doc, "startup.process", "driver")[0]["start"]
    monkeypatch.setattr(sys.modules["__main__"], "T_PROCESS_START",
                        began + 0.2, raising=False)
    return _trace()


def test_every_operation_lands_in_exactly_one_bucket():
    trace, report = _trace(), _timeline()["programs"]["train.step"]
    (plane,) = trace.device_planes()
    (run,) = part_lib.module_events(trace, plane, report["module"])
    ops = part_lib.operations(trace, report, plane)
    inside = [e for e in trace.self_times(plane)
              if run[0] <= e[1] < run[1]]
    assert len(ops) == len(inside) > 2000
    assert {op["bucket"] for op in ops} <= set(part_lib.BUCKETS)
    tiled = part_lib.tile(trace, report)
    assert sum(tiled["calls"].values()) == len(ops)
    # the recording and the report are of one program: every operation the
    # trace shows has its row
    assert not [op["event"] for op in ops if op["row"] is None]
    assert tiled["unjoined_ms"] == 0.0
    # a kernel is a kernel of attention or of the routed experts
    kernels = [op for op in ops if op["row"][3] == part_lib.KERNEL_TARGET]
    assert {op["bucket"] for op in kernels} == {"attention_kernels",
                                               "routed_kernels"}
    # 3 segments x (forward, remat's, backward) a layer run: 5 layers
    assert sum(op["bucket"] == "attention_kernels" for op in kernels) == 15


def test_the_buckets_and_the_idle_time_tile_the_step_module():
    trace, report = _trace(), _timeline()["programs"]["train.step"]
    tiled = part_lib.tile(trace, report)
    step = trace.program_time(r"_step_fn")      # what `step_ms.swamoe` reads
    assert tiled["steps"] == step["count"] == 1
    assert tiled["step_ms"] == pytest.approx(step["seconds"] * 1e3, rel=1e-9)
    total = sum(tiled["parts"].values()) + tiled[part_lib.IDLE]
    assert total == pytest.approx(tiled["step_ms"], rel=1e-9)
    assert tiled[part_lib.IDLE] >= 0.0
    assert tiled["parts"]["collectives"] == 0.0     # one chip
    assert tiled["parts"]["scan"] == 0.0            # no state-space layer
    by_scope = tiled["by_scope"]
    assert sum(v["ms"] for v in by_scope.values()) == pytest.approx(
        sum(tiled["parts"].values()), rel=1e-9)
    assert {sc for (b, sc) in by_scope if b == "routed_xla"} == {
        "moe.route", "moe.dispatch", "moe.experts", "moe.combine"}


@pytest.mark.parametrize("name", NEW)
def test_reader_on_the_recorded_run(name, recorded_run):
    reader = harness.load_layer_metrics()[name]
    value = reader.read([], recorded_run, {}, CELL)
    tiled = part_lib.tile(recorded_run,
                          _timeline()["programs"]["train.step"])
    if name == "step_program_hbm_share":
        report = _timeline()["programs"]["train.step"]
        assert value == pytest.approx(
            100.0 * report["memory"]["total_bytes"] / report["bytes_limit"])
        assert 85.0 < value < 100.0     # AOT: 14.727 GiB of 15.75
    elif name == "bare_copy_ms":
        assert value == tiled["bare_copy_ms"] > 0
    elif name == "part_ms.idle_in_program":
        assert value == tiled[part_lib.IDLE]
    else:
        assert value == tiled["parts"][name.split(".", 1)[1]]
    if CELL["cell"]["name"] in reader.WORKLOADS \
            and name != "part_ms.idle_in_program":
        assert value > 0


def test_the_readers_sum_to_the_step_and_no_model_code_is_unscoped(
        recorded_run):
    readers = harness.load_layer_metrics()
    step = readers["step_ms.swamoe"].read([], recorded_run, {}, CELL)
    parts = {n: readers[n].read([], recorded_run, {}, CELL)
             for n in NEW if n.startswith("part_ms.")}
    # the collectives' bucket is the fourteenth part; 0 on one chip
    assert sum(parts.values()) == pytest.approx(step, rel=5e-3)
    # the cell as PERF.md section 5 has it (PR 39's traced run)
    assert parts["part_ms.attention_kernels"] == pytest.approx(98.4, rel=0.01)
    assert parts["part_ms.routed_kernels"] == pytest.approx(20.1, rel=0.01)
    # What no scope names is 7.1 % of this step, and none of it is the
    # model's code: operations the compiler made and gave no `op_name`
    # (async copies' waits, the stacked parameters' casts hoisted out of
    # the layer loop, relayouts) and `lax.scan`'s own (a layer's slice of
    # the stacks, the gradients' stacking and zero fill).  A layer's body
    # runs under `closed_call`: of that, under 0.5 % of the step is loose.
    assert 0.03 * step < parts["part_ms.unscoped"] < 0.08 * step
    report = _timeline()["programs"]["train.step"]
    (plane,) = recorded_run.device_planes()
    loose = [op for op in part_lib.operations(recorded_run, report, plane)
             if op["bucket"] == part_lib.UNSCOPED]
    in_a_layer = sum(op["self"] for op in loose
                     if "closed_call" in op["row"][1]) / 1e6
    unnamed = sum(op["self"] for op in loose if not op["row"][1]) / 1e6
    assert in_a_layer < 0.005 * step
    assert unnamed > 0.5 * parts["part_ms.unscoped"]
    assert not [op["name"] for op in loose
                if op["row"][2] or op["row"][3] == part_lib.KERNEL_TARGET]


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_nothing_without_a_report(name, tmp_path, monkeypatch,
                                               recorded_run):
    """The parent commit writes a timeline.json without `programs`, an
    untraced or rehearsed run has no trace, a stale file is no reading:
    None each time, and no exception."""
    reader = harness.load_layer_metrics()[name]
    assert reader.read([], None, {}, CELL) is None or \
        name == "step_program_hbm_share"    # the report alone feeds it
    path = os.path.join(harness.OUT_DIR, "train", CELL["cell"]["name"],
                        "timeline.json")
    doc = _timeline()
    del doc["programs"]
    with open(path, "w") as f:
        json.dump(doc, f)
    fresh = _trace()     # a reader keeps its tiling on the trace
    assert reader.read([], fresh, {}, CELL) is None
    os.remove(path)
    assert reader.read([], _trace(), {}, CELL) is None
    assert reader.read([], None, {}, CELL) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_is_what_benchmark_json_says(name):
    reader = harness.load_layer_metrics()[name]
    (entry,) = [m for m in harness.load_benchmark()["per_layer"]
                if m["name"] == name]
    assert entry == {"name": reader.NAME, "unit": reader.UNIT,
                     "better": "lower", "source": reader.SOURCE,
                     "layer": reader.LAYER, "moves": reader.MOVES,
                     "workloads": reader.WORKLOADS}
    assert reader.SOURCE == "device_trace"
    assert reader.MOVES == "train_tokens_per_s"
    cells = {w["name"] for w in harness.load_benchmark()["workloads"]}
    assert set(reader.WORKLOADS) <= cells


def test_the_thirteen_are_the_last_entries_and_have_no_twins():
    per_layer = harness.load_benchmark()["per_layer"]
    assert [m["name"] for m in per_layer[-13:]] == list(NEW)
    files = {f for f in os.listdir(os.path.join(
        harness.ROOT, "benchmark", "layer_metrics")) if f.endswith(".py")}
    assert {n + ".py" for n in NEW} <= files
    assert not [f for f in files if f.startswith(("part_ms.", "bare_copy"))
                and f[:-3] not in NEW]


def test_rules_put_collectives_first_then_the_innermost_scope():
    row = ["all-gather-start", "jit(_step_fn)/jit(main)/attn.full/x", False,
           ""]
    assert part_lib.bucket_of(row, "all-gather-start") == "collectives"
    assert part_lib.bucket_of(None, "all-reduce") == "collectives"
    assert part_lib.bucket_of(None, "fusion") == "unscoped"
    under = "jit(_step_fn)/jit(main)/transpose(jvp(while))/body/checkpoint/"
    kernel = ["custom-call", under + "attn.sliding/pallas_call", False,
              "tpu_custom_call"]
    assert part_lib.bucket_of(kernel, "custom-call") == "attention_kernels"
    kernel[1] = under + "moe.dispatch/cond/branch_1_fun/moe.experts/pallas"
    assert part_lib.bucket_of(kernel, "custom-call") == "routed_kernels"
    fusion = ["fusion", under + "moe.dispatch/cond/branch_1_fun/moe.experts/"
              "mul", False, ""]
    assert part_lib.bucket_of(fusion, "fusion") == "routed_xla"
    fusion[1] = under + "attn.full/attn.gate/dot_general"
    fusion[2] = True
    assert part_lib.bucket_of(fusion, "fusion") == "attention_proj"
    fusion[1] = under + "gmu/dot_general"
    assert part_lib.bucket_of(fusion, "fusion") == "mlp"
    fusion[1] = under + "ssm/dot_general"
    assert part_lib.bucket_of(fusion, "fusion") == "scan"
    # an AllocateBuffer is a custom call and no kernel
    alloc = ["custom-call", under + "mla.project/x", False, "AllocateBuffer"]
    assert part_lib.bucket_of(alloc, "custom-call") == "attention_glue"
    assert part_lib.is_bare_copy("copy.3", ["copy", "", False, ""], "copy")
    assert part_lib.is_bare_copy("convert_bitcast_fusion.2",
                                 ["fusion", "", False, ""], "fusion")
    assert not part_lib.is_bare_copy("convert_reduce_fusion.4",
                                     ["fusion", "", False, ""], "fusion")
