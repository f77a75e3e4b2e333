"""The step program's report (PR 39): what `count_compiles` keeps of a
program's first call, what `device_stats.program_report` makes of it, when
`TrainWorker.timeline()` carries it, and the table `scripts/opsdump.py
--parts` prints from it and a trace.  All on the CPU, tiny programs."""

import glob
import json
import os
import sys
import tempfile

import pytest

from ray_tpu.util import device_stats, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))

TINY = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
            num_layers=1, num_heads=2, num_kv_heads=2, max_seq_len=16)


@pytest.fixture(autouse=True)
def _untraced():
    tracing.disable_tracing()
    yield
    tracing.disable_tracing()


def _scoped_program():
    import jax
    import jax.numpy as jnp

    def loss(x, w):
        with jax.named_scope("mlp"):
            y = x @ w
        with jax.named_scope("loss"):
            return jnp.sum(jax.nn.silu(y))

    def three_steps(x, w):
        def body(carry, _):
            return carry - 0.1 * jax.grad(loss)(carry, w), None

        return jax.lax.scan(body, x, None, length=3)[0]

    return jax.jit(three_steps), (jnp.ones((64, 64)), jnp.ones((64, 64)))


def test_program_report_gives_instructions_scopes_and_memory():
    fn, args = _scoped_program()
    tracked = device_stats.count_compiles(fn, "unit.report")
    tracked(*args)
    report = device_stats.program_report("unit.report")
    assert report["module"] == "jit_three_steps"
    rows = report["instructions"]
    dots = {n: r for n, r in rows.items() if r[0] == "dot"}
    # the backward's matmul keeps the scope, inside transpose(jvp(..))
    assert dots and all(r[2] for r in dots.values())
    assert any("transpose(jvp(mlp))" in r[1] for r in dots.values())
    assert any(r[0] == "while" for r in rows.values())
    assert any("jvp(loss)" in r[1] for r in rows.values())
    # the loop's body is there; what no trace shows is not
    assert not {r[0] for r in rows.values()} & {
        "parameter", "constant", "tuple", "get-tuple-element", "bitcast"}
    assert all(len(r) == 4 and isinstance(r[2], bool) for r in rows.values())
    m = report["memory"]
    assert m["argument_bytes"] == 2 * 64 * 64 * 4
    assert m["output_bytes"] == 64 * 64 * 4
    assert m["total_bytes"] == (m["argument_bytes"] + m["output_bytes"]
                                - m["alias_bytes"] + m["temp_bytes"])
    assert report["bytes_limit"] is None        # the CPU reports none
    assert report["seconds"] > 0
    json.dumps(report)                          # timeline.json holds it


def test_program_report_knows_only_programs_that_were_called():
    fn, _ = _scoped_program()
    device_stats.count_compiles(fn, "unit.never_called")
    assert device_stats.program_report("unit.never_called") is None
    assert device_stats.program_report("unit.no_such_program") is None


def test_the_first_calls_signature_is_kept_once():
    import jax.numpy as jnp

    fn, args = _scoped_program()
    tracked = device_stats.count_compiles(fn, "unit.signature")
    assert tracked._signature is None
    tracked(*args)
    kept = tracked._signature
    shapes, kwargs, mesh = kept
    assert [(a.shape, a.dtype) for a in shapes] == [
        ((64, 64), jnp.float32)] * 2 and kwargs == {} and mesh is None
    assert all(not hasattr(a, "addressable_shards") for a in shapes)
    tracked(*args)
    tracked(jnp.ones((8, 64)), args[1])         # a second shape compiles,
    assert tracked._signature is kept           # the report stays the first's
    assert device_stats.compile_counts()["unit.signature"]["count"] == 2


HAND_MADE_HLO = """\
HloModule jit__step_fn, is_scheduled=true

%fused_computation.1 (p0: bf16[8,128], p1: bf16[128,128]) -> bf16[8,128] {
  %p0 = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(0)
  %p1 = bf16[128,128]{1,0:T(8,128)(2,1)} parameter(1)
  ROOT %convolution.1 = bf16[8,128]{1,0:T(8,128)(2,1)} convolution(%p0, %p1), dim_labels=bf_io->bf
}

%fused_computation.2 (p0: bf16[8,128]) -> bf16[8,128] {
  %p0.1 = bf16[8,128]{1,0} parameter(0)
  ROOT %negate.1 = bf16[8,128]{1,0} negate(%p0.1)
}

%branch_a (arg: (bf16[8,128])) -> bf16[8,128] {
  %arg = (bf16[8,128]{1,0}) parameter(0)
  %gte.1 = bf16[8,128]{1,0} get-tuple-element(%arg), index=0
  ROOT %kernel.1 = bf16[8,128]{1,0} custom-call(%gte.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step_fn)/jit(main)/moe.dispatch/cond/branch_0_fun/moe.experts/pallas_call" source_file="/x/moe.py" source_line=3}
}

%branch_b (arg.1: (bf16[8,128])) -> bf16[8,128] {
  %arg.1 = (bf16[8,128]{1,0}) parameter(0)
  ROOT %gte.2 = bf16[8,128]{1,0} get-tuple-element(%arg.1), index=0
}

%body (c: (s32[], bf16[8,128])) -> (s32[], bf16[8,128]) {
  %c = (s32[], bf16[8,128]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%c), index=0
  %x = bf16[8,128]{1,0} get-tuple-element(%c), index=1
  %w = bf16[128,128]{1,0} constant({...})
  %fusion.1 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%x, %w), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(_step_fn)/jit(main)/while/body/checkpoint/attn.full/dot_general"}
  %fusion.2 = bf16[8,128]{1,0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(_step_fn)/jit(main)/while/body/checkpoint/attn.full/attn.gate/neg"}
  %t = (bf16[8,128]{1,0}) tuple(%fusion.2)
  %conditional.1 = bf16[8,128]{1,0} conditional(%i, %t, %t), branch_computations={%branch_a, %branch_b}, metadata={op_name="jit(_step_fn)/jit(main)/while/body/moe.dispatch/cond"}
  ROOT %out = (s32[], bf16[8,128]{1,0}) tuple(%i, %conditional.1)
}

%cond (c.1: (s32[], bf16[8,128])) -> pred[] {
  %c.1 = (s32[], bf16[8,128]{1,0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%c.1), index=0
  %n = s32[] constant(3)
  ROOT %compare.1 = pred[] compare(%i.1, %n), direction=LT
}

ENTRY %main.1 (a: bf16[8,128]) -> bf16[8,128] {
  %a = bf16[8,128]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], bf16[8,128]{1,0}) tuple(%zero, %a)
  %while.1 = (s32[], bf16[8,128]{1,0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(_step_fn)/jit(main)/while"}
  %res = bf16[8,128]{1,0} get-tuple-element(%while.1), index=1
  %all-reduce.1 = bf16[8,128]{1,0} all-reduce(%res), replica_groups={}, to_apply=%branch_b, metadata={op_name="jit(_step_fn)/jit(main)/optimizer/psum"}
  ROOT %copy.1 = bf16[8,128]{1,0} copy(%all-reduce.1)
}
"""


def test_hlo_instructions_walks_loops_and_branches_not_fusions():
    module, rows = device_stats.hlo_instructions(HAND_MADE_HLO)
    assert module == "jit__step_fn"
    assert set(rows) == {"while.1", "all-reduce.1", "copy.1", "fusion.1",
                         "fusion.2", "conditional.1", "kernel.1",
                         "compare.1"}
    assert rows["fusion.1"] == [
        "fusion", "jit(_step_fn)/jit(main)/while/body/checkpoint/attn.full/"
        "dot_general", True, ""]
    assert rows["fusion.2"][2] is False
    assert rows["kernel.1"][0] == "custom-call"
    assert rows["kernel.1"][3] == "tpu_custom_call"
    assert rows["kernel.1"][1].endswith("moe.experts/pallas_call")
    assert rows["copy.1"] == ["copy", "", False, ""]
    # an all-reduce's reducer is not a computation the device runs as ops
    assert "gte.2" not in rows and "negate.1" not in rows


def _tiny_train_step():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import transformer as tfm
    from ray_tpu.parallel.mesh import build_mesh
    from ray_tpu.train.train_state import ShardedTrainStep

    mesh = build_mesh(axes={"data": 1}, devices=jax.devices()[:1])
    return (ShardedTrainStep(tfm.TransformerConfig(**TINY), mesh),
            {"tokens": jnp.zeros((2, 17), jnp.int32)})


@pytest.fixture(scope="module")
def stepped():
    """A tiny train step that has run twice in this process, untraced."""
    import jax

    ts, batch = _tiny_train_step()
    state = ts.init(jax.random.key(0))
    for _ in range(2):
        state, metrics = ts.step(state, batch)
    float(metrics["loss"])
    return ts, state, batch


def _worker(rank=0):
    from ray_tpu.train.worker_group import TrainWorker

    return TrainWorker(rank, 2, tempfile.mkdtemp(prefix="report-"))


def test_an_untraced_worker_lowers_nothing_and_reports_no_program(
        stepped, monkeypatch):
    monkeypatch.setattr(tracing, "_profile_seen", False)
    before = device_stats.compile_totals()
    part = _worker().timeline()
    assert "programs" not in part
    assert part["compile_totals"] == before
    # no trace, no lowering, no compile happened for it
    assert device_stats.compile_totals() == before


def test_a_traced_worker_reports_the_step_program_rank_0_only(
        stepped, monkeypatch):
    from ray_tpu.models import common

    monkeypatch.setattr(tracing, "_profile_seen", False)
    tracing.enable_tracing()
    before = device_stats.compile_totals()
    part = _worker().timeline()
    report = part["programs"]["train.step"]
    assert report["module"] == "jit__step_fn"
    # the totals the timeline carries are the RUN's: taken before the
    # report's own lowering and compile
    assert part["compile_totals"] == before
    assert device_stats.compile_totals()["trace_lower_s"] \
        > before["trace_lower_s"]
    names = " ".join(r[1] for r in report["instructions"].values())
    for scope in (common.ATTN_FULL, common.MLP, common.LOSS, common.EMBED,
                  common.OPTIMIZER):
        assert scope in names, scope
    matmuls = [r for r in report["instructions"].values() if r[2]]
    assert matmuls and all(
        any(s in r[1] for s in common.SCOPES) for r in matmuls), matmuls
    assert "programs" not in _worker(rank=1).timeline()


def test_a_profile_turns_the_report_on_as_it_turns_the_spans_on(
        stepped, monkeypatch):
    """PR 27's rule: "tracing on" is "a profile is running"."""
    import jax

    ts, state, batch = stepped
    monkeypatch.setattr(tracing, "_profile_seen", False)
    assert not tracing.profile_seen() and not tracing.is_tracing_enabled()
    d = tempfile.mkdtemp(prefix="profile-")
    jax.profiler.start_trace(d)
    try:
        state, metrics = ts.step(state, batch)
        float(metrics["loss"])
    finally:
        jax.profiler.stop_trace()
    assert glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))
    assert tracing.profile_seen()
    assert "train.step" in _worker().timeline()["programs"]


# -- opsdump --parts ---------------------------------------------------------

def _hand_made_run(tmp_path):
    """A trace of two runs of the step module on one device and the report
    of HAND_MADE_HLO, as files."""
    from benchmark import trace_reduce

    def line(name, rest):
        return f"%{name} = bf16[8,128] {rest}"

    ops, t = [], 100.0
    for run in range(2):
        base = 100.0 + run * 1000.0
        ops += [
            (line("while.1", "while(%init)"), base, 700.0, ""),
            (line("fusion.1", "fusion(%x, %w)"), base + 10, 200.0, ""),
            (line("fusion.2", "fusion(%fusion.1)"), base + 220, 100.0, ""),
            (line("conditional.1", "conditional(%i, %t, %t)"),
             base + 330, 300.0, ""),
            (line("kernel.1", "custom-call(%gte.1)"), base + 350, 250.0, ""),
            (line("all-reduce.1", "all-reduce(%res)"), base + 710, 40.0, ""),
            (line("copy.1", "copy(%all-reduce.1)"), base + 760, 20.0, ""),
            (line("stray.9", "add(%a, %a)"), base + 790, 10.0, ""),
        ]
    view = trace_reduce.TraceView({"/device:TPU:0": {
        "XLA Modules": [("jit__step_fn(123)", 100.0, 800.0, ""),
                        ("jit__step_fn(123)", 1100.0, 800.0, ""),
                        ("jit_other(7)", 2000.0, 50.0, "")],
        "XLA Ops": ops + [(line("fusion.1", "fusion(%x)"), 2010.0, 30.0, "")],
    }})
    trace = str(tmp_path / "trace.json")
    view.to_json(trace)
    module, rows = device_stats.hlo_instructions(HAND_MADE_HLO)
    timeline = str(tmp_path / "timeline.json")
    with open(timeline, "w") as f:
        json.dump({"spans": [], "programs": {"train.step": {
            "module": module, "instructions": rows, "seconds": 1.5,
            "bytes_limit": 16 * 2 ** 30,
            "memory": {"argument_bytes": 8 * 2 ** 30,
                       "output_bytes": 8 * 2 ** 30,
                       "alias_bytes": 8 * 2 ** 30,
                       "temp_bytes": 4 * 2 ** 30,
                       "generated_code_bytes": 0,
                       "total_bytes": 12 * 2 ** 30}}}}, f)
    return trace, timeline


def test_opsdump_parts_tiles_a_hand_made_step(tmp_path, capsys):
    import opsdump

    trace, timeline = _hand_made_run(tmp_path)
    assert opsdump.main(["--xplane", trace, "--timeline", timeline,
                         "--parts"]) == 0
    out = capsys.readouterr().out
    rows = {l.split()[0]: l.split() for l in out.splitlines()[2:]
            if l and not l.startswith(("bare", "memory", "  unscoped:"))}
    ns = 1e-6   # the table is in ms; the hand-made times are ns
    assert "module jit__step_fn, 2 steps a device on 1 devices" in out
    assert float(rows["attention_proj"][1]) == pytest.approx(200 * ns, abs=1e-2)
    # every operation in exactly one part; the parts and the idle time
    # tile the module's 800 ns:
    #   fusion.1 200 (projection)  fusion.2 100 (glue: attn.gate, no matmul)
    #   kernel.1 250 (routed kernel)  conditional.1 300 - 250 (routed XLA)
    #   while.1 700 - 600 and stray.9 10, which the report does not know
    #   (unscoped)  all-reduce.1 40 (collectives, whatever its scope)
    #   copy.1 20 (unscoped, and a bare copy)  idle 800 - 770
    from benchmark import part_lib, trace_reduce

    tiled = part_lib.tile(trace_reduce.from_json(trace), json.load(
        open(timeline))["programs"]["train.step"])
    assert tiled["steps"] == 2 and tiled["step_ms"] == pytest.approx(800 * ns)
    assert {k: round(v / ns) for k, v in tiled["parts"].items() if v} == {
        "attention_proj": 200, "attention_glue": 100, "routed_kernels": 250,
        "routed_xla": 50, "unscoped": 130, "collectives": 40}
    assert tiled["idle_in_program"] == pytest.approx(30 * ns)
    assert sum(tiled["parts"].values()) + tiled["idle_in_program"] \
        == pytest.approx(tiled["step_ms"])
    assert tiled["bare_copy_ms"] == pytest.approx(20 * ns)
    assert tiled["unjoined_ms"] == pytest.approx(10 * ns)
    assert "memory a chip: 12.000 GiB of 16.00 (75.0 %)" in out
    assert "the report took 1.5 s" in out
    assert rows["sum"][2] == "100.0%"


def test_opsdump_parts_needs_a_traced_runs_timeline(tmp_path):
    import opsdump

    trace, _ = _hand_made_run(tmp_path)
    untraced = str(tmp_path / "untraced.json")
    with open(untraced, "w") as f:
        json.dump({"spans": [], "compiles": [], "compile_totals": {}}, f)
    with pytest.raises(SystemExit, match="only a traced run"):
        opsdump.main(["--xplane", trace, "--timeline", untraced, "--parts"])
    with pytest.raises(SystemExit):
        opsdump.main(["--xplane", trace, "--parts"])


def test_the_benchmarks_rules_name_every_scope_of_the_vocabulary():
    """The scopes are written twice, in the program (models/common.py) and
    in the benchmark's rules (a reader must also run on a program that has
    none): the two lists are one."""
    from benchmark import part_lib
    from ray_tpu.models import common

    assert set(part_lib.SCOPE_BUCKETS) == set(common.SCOPES)
    assert len(set(common.SCOPES)) == len(common.SCOPES)
    assert part_lib.scope_of(
        "jit(_step_fn)/jit(main)/transpose(jvp(while))/body/checkpoint/"
        "attn.full/attn.gate/mul") == "attn.gate"
    assert part_lib.scope_of("jit(_step_fn)/jit(main)/jvp(mlp)/dot") == "mlp"
    assert part_lib.scope_of("jit(_step_fn)/jit(main)/mlp_norm/x") is None
    assert part_lib.STEP_PROGRAM == "train.step"
