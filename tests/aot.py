"""What the tests/test_tpu_aot_compile*.py files share: how a v5e is
described with no chip attached, how a cell's whole step program is compiled
for it, and how a compiled program is read (its Mosaic calls as a trace
names them, its bytes, its text less what names the source).

No chip is attached: the TPU compiler installed here compiles for a
topology that is described (v5e:2x2).  What the chip's compiler would refuse
(tiling, VMEM, partitioning) it refuses here, at no chip time.  A compile
that passes is not a chip run.  The topology is described inside a fixture
(tests/conftest.py's `topo`): only an xdist worker that is handed such a
file loads libtpu.  Everything compiles in the test's own process, with the
persistent compile cache off (an entry written for a described chip cannot
be read back without one).

A new cell's step program is ONE new file whose module-scoped fixture calls
`compile_step`: `--dist loadfile` gives a file to one worker, so a file a
compile is what spreads the compiles over the workers.
"""

import base64
import copy
import hashlib
import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops import attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def describe_v5e():
    """The body of the `topo` fixture: yields the described v5e:2x2, the
    compile cache off while it is in use."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def on_tpu(monkeypatch, module=attention):
    """The ops pick their path and interpret mode from the live backend (the
    CPU, here); steer them in the test, not through an option of the
    program.  `dispatch` is the one module of all ops."""
    monkeypatch.setattr(module.dispatch, "platform", lambda: "tpu")
    monkeypatch.setattr(module.dispatch, "interpret_mode", lambda: False)


def config_doc(config_file):
    """A cell's configuration under benchmark/configs/, by file name."""
    with open(os.path.join(ROOT, "benchmark", "configs", config_file)) as f:
        return json.load(f)


def compile_step(topo, config_file, **lower_kwargs):
    """(a cell's whole step program as `ShardedTrainStep` jits it, compiled
    for one chip of the described v5e; what its trace left in
    `dispatch.taken()`; the configuration's train group; the number of
    parameters).  config_file: a name under benchmark/configs/.
    lower_kwargs: `_step_fn`'s static arguments (`keep=True`: the ladder's
    first rung).  A whole step takes a minute or two: call it from a
    module-scoped fixture, so that every test of the step shares the one
    compile."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark.drivers import train_model
    from ray_tpu.train.train_state import ShardedTrainStep, default_optimizer

    doc = config_doc(config_file)
    tr = doc["train"]
    config = train_model.build_config(doc["program"], doc["model"], tr)
    mesh = Mesh(topo.devices[:1], ("fsdp",))
    whole = NamedSharding(mesh, P())
    ts = ShardedTrainStep(config, mesh, optimizer=default_optimizer(
        warmup_steps=tr["lr_warmup_steps"], total_steps=tr["lr_total_steps"],
        mu_dtype=jnp.bfloat16, nu_dtype=jnp.bfloat16))
    key = jax.eval_shape(lambda: jax.random.key(0))
    with pytest.MonkeyPatch.context() as mp:
        on_tpu(mp)
        mp.setattr(attention.dispatch, "_taken", {})
        with jax.sharding.set_mesh(mesh):
            state = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=whole),
                jax.eval_shape(ts._init_fn, key))
            batch = {"tokens": jax.ShapeDtypeStruct(
                (tr["batch_rows"], tr["sequence_length"] + 1), jnp.int32,
                sharding=whole)}
            compiled = jax.jit(
                ts._step_fn, donate_argnums=(0,),
                static_argnames=tuple(lower_kwargs),
            ).lower(state, batch, **lower_kwargs).compile()
        taken = copy.deepcopy(attention.dispatch.taken())
    count = sum(a.size for a in jax.tree.leaves(state["params"]))
    return compiled, taken, tr, count


def face(family, name):
    """The pattern (or patterns) by which the benchmark's readers find a
    kernel of a family's cells in a trace: an HLO line's result and first
    operands, or the kernel's name in `op_name`."""
    return importlib.import_module(
        f"benchmark.families.{family}").KERNELS[name]


def _every_face():
    """name -> pattern: every face a reader of the expert cells looks for."""
    from benchmark import gdn_faces, moe_faces, swa_moe_faces

    return {f"{m.__name__}.{n}": p for m in (moe_faces, gdn_faces,
                                             swa_moe_faces)
            for n, p in vars(m).items()
            if n.isupper() and n[0] != "_" and isinstance(p, str)}


def _grouped_calls(calls):
    """How many of a program's custom calls the grouped readers find."""
    from benchmark import moe_faces

    return sum(bool(re.search(p, l)) for l in calls
               for p in (moe_faces.GROUPED_FORWARD,
                         moe_faces.GROUPED_TRANSPOSED, moe_faces.GROUPED_DW))


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _custom_calls_of(compiled):
    """A compiled module's Mosaic custom-call lines, printed the way
    the profiler names an operation in a trace: result and operand
    shapes, no layouts."""
    from jax._src.lib import _jax

    opts = _jax.HloPrintOptions.short_parsable()
    opts.print_operand_shape = True
    opts.include_layout_in_shapes = False
    opts.print_backend_config = False
    text = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    return [l for l in text.splitlines() if "tpu_custom_call" in l]


def _custom_calls_as_traced(fn, *shapes):
    return _custom_calls_of(jax.jit(fn).lower(*shapes).compile())


def _kernel_op_names(compiled):
    """The `op_name` of every Mosaic call of a compiled module: the scopes
    it lies in and, last but one, the kernel's own name."""
    return [re.search(r'op_name="([^"]*)"', l).group(1)
            for l in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in l]


def _chip_bytes(compiled) -> int:
    from ray_tpu.util.device_stats import program_bytes

    return program_bytes(compiled.memory_analysis())


_DTYPE_BYTES = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "pred": 1}


def _hlo_bytes(shapes: str) -> int:
    total = 0
    for dtype, dims in re.findall(r"\b(f32|bf16|s32|u32|pred)\[([0-9,]*)\]",
                                  shapes):
        n = 1
        for x in filter(None, dims.split(",")):
            n *= int(x)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _kernel_without_locations(body: str) -> str:
    """sha256 of a Mosaic kernel (a custom call's `body`: base64 of MLIR
    bytecode) printed without its debug locations, which hold the CALL
    SITE's file, function and line in the model files."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        asm = ir.Module.parse(base64.b64decode(body)).operation.get_asm(
            enable_debug_info=False)
    return hashlib.sha256(asm.encode()).hexdigest()


def _metadata_stripped(text: str) -> str:
    """A compiled module's text less what names its source: every
    instruction's `metadata={...}`, the tables of files, functions,
    locations and stack frames between the header and the first
    computation, the locations inside each Mosaic kernel, and the
    instructions' own names."""
    lines = text.splitlines()
    if "FileNames" in lines:
        first = lines.index("FileNames")
        del lines[first:next(i for i in range(first, len(lines))
                             if lines[i].startswith(("%", "ENTRY ")))]
    text = re.sub(r',? ?metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}', "",
                  "\n".join(lines))
    text = re.sub(
        r'"body":"([A-Za-z0-9+/=]+)"',
        lambda m: f'"body":"{_kernel_without_locations(m.group(1))}"', text)
    # An instruction's NAME comes from its source too (`%jit__scan_fwd_.26`
    # is the call's, the number whatever made the name unique): each name
    # becomes its rank by first appearance, which keeps who feeds whom.
    rank = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: rank.setdefault(m.group(0), f"%{len(rank)}"),
                  text)


def hlo_is_as_recorded(text: str, sha256: str):
    """A compiled program's optimised HLO, `_metadata_stripped`, is the one
    whose digest the test file holds.  The rule of every
    `PARENT_HLO_SHA256`: a scope is metadata and may move no fusion, no
    schedule and no byte of a kernel; a change that MEANS to move the
    program replaces its digest and says so beside it."""
    text = _metadata_stripped(text)
    assert "op_name" not in text and "source_file" not in text \
        and ".py" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == sha256


def _scope_pattern(*more):
    from ray_tpu.models import common

    return re.compile(r"(?<![\w.])(" + "|".join(
        re.escape(s) for s in (*common.SCOPES, *more)) + r")(?![\w.])")


def every_matmul_and_kernel_is_scoped(text: str, whole_step: bool):
    """What the `part_ms.*` readers rest on: in the compiled program every
    instruction a trace can show that is a Pallas kernel or holds a matmul
    (a fusion's root gives it its `op_name`) names one of
    `models/common.py`'s scopes, forward, remat's second forward and
    backward alike; a whole step also holds both ends and the rest."""
    from ray_tpu.models import common
    from ray_tpu.util.device_stats import hlo_instructions

    scope = _scope_pattern()
    module, rows = hlo_instructions(text)
    assert module.startswith("jit_")
    heavy = {name: row for name, row in rows.items()
             if row[2] or row[3] == "tpu_custom_call"}
    assert len(heavy) >= 10, sorted(heavy)
    bare = {name: row[1] for name, row in heavy.items()
            if not scope.search(row[1])}
    assert not bare, bare
    if whole_step:
        found = {s for row in rows.values() for s in scope.findall(row[1])}
        assert {common.EMBED, common.LOSS, common.OPTIMIZER,
                common.MLP} <= found, found
