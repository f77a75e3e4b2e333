"""AOT-compile the `train-cca-moe-d4` cell's step program for a described
v5e (ZAYA1-8B's widths, four layers, all sixteen experts, 1 x 8192 tokens):
its bytes, its kernels' plans, and what the cell's readers find it by.

No chip is attached: the TPU compiler installed here compiles for a
topology that is described (v5e:2x2).  A compile that passes is not a chip
run.  The topology is described inside a fixture, as in
tests/test_tpu_aot_compile.py, whose wall time this file stays out of: only
the xdist worker that is handed this file loads libtpu here, everything
compiles in the test's own process, with the persistent compile cache off.
"""

import copy
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops import attention
from test_tpu_aot_compile import _metadata_stripped

CONFIG = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                      "benchmark", "configs", "zaya1-8b-train-d4.json")


@pytest.fixture(scope="module")
def topo():
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


@pytest.fixture(scope="module")
def step_program(topo):
    """(the cell's whole step program as `ShardedTrainStep` jits it on the
    ladder's FIRST rung, each layer's flash out and lse kept, which is the
    one the chip takes; what its trace left in `dispatch.taken()`; the
    configuration's train group).  One compile, about a minute."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark.drivers import train_model
    from ray_tpu.train.train_state import ShardedTrainStep, default_optimizer

    with open(CONFIG) as f:
        doc = json.load(f)
    tr = doc["train"]
    config = train_model.build_config(doc["program"], doc["model"], tr)
    mesh = Mesh(topo.devices[:1], ("fsdp",))
    whole = NamedSharding(mesh, P())
    ts = ShardedTrainStep(config, mesh, optimizer=default_optimizer(
        warmup_steps=tr["lr_warmup_steps"], total_steps=tr["lr_total_steps"],
        mu_dtype=jnp.bfloat16, nu_dtype=jnp.bfloat16))
    key = jax.eval_shape(lambda: jax.random.key(0))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(attention.dispatch, "platform", lambda: "tpu")
        mp.setattr(attention.dispatch, "interpret_mode", lambda: False)
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
                ts._step_fn, donate_argnums=(0,), static_argnames=("keep",)
            ).lower(state, batch, keep=True).compile()
        taken = copy.deepcopy(attention.dispatch.taken())
    return compiled, taken, tr


# sha256 of the step program's optimised HLO, `_metadata_stripped`, as PR
# 46's tree (40fa1e4) compiled it: tests/test_tpu_aot_compile.py's
# `PARENT_HLO_SHA256` has the rule (a change that means to move the program
# replaces the digest and says so) and the other cells'.  PR 50 MEANT TO:
# the grouped kernels' forward / transposed grid walks a column block's row
# tiles before the next column block and the 2048 x 2048 matrix is ONE block
# (PR 46's tree read 2d09fc2a..).
PARENT_HLO_SHA256 = (
    "80b748b99e62eb2171c103fe147284e3439f32ea2a6216d5e70600fbff9fe333")


def test_cell_cca_moe_optimised_hlo_is_as_the_parent_compiled_it(
        step_program):
    import hashlib

    text = _metadata_stripped(step_program[0].as_text())
    assert "op_name" not in text and "source_file" not in text \
        and ".py" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_HLO_SHA256


def _calls_as_traced(compiled):
    """The compiled module's Mosaic custom-call lines, printed the way the
    profiler names an operation in a trace: result and operand shapes, no
    layouts."""
    from jax._src.lib import _jax

    opts = _jax.HloPrintOptions.short_parsable()
    opts.print_operand_shape = True
    opts.include_layout_in_shapes = False
    opts.print_backend_config = False
    text = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    return [l for l in text.splitlines() if "tpu_custom_call" in l]


def _kernel_op_names(compiled):
    return [re.search(r'op_name="([^"]*)"', l).group(1)
            for l in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in l]


def test_cell_cca_moe_step_program_fits_a_v5e(step_program):
    """The cell's whole step program (four layers of compressed
    convolutional attention and sixteen held experts of 2048, an eighth of
    the tied vocabulary, 1 x 8192 tokens, out and lse kept across remat,
    fused CE, bfloat16 moments) by AOT memory_analysis: under 15.75 GiB at
    the configuration's rows, and over 13 (the state alone is 11.7)."""
    from ray_tpu.util.device_stats import program_bytes

    compiled, taken, tr = step_program
    assert tr["batch_rows"] == 1 and tr["sequence_length"] == 8192
    total = program_bytes(compiled.memory_analysis())
    assert 13.0 * 2 ** 30 < total < 15.0 * 2 ** 30, total / 2 ** 30
    # one segment: the flash forward (kept: no second one) and backward,
    # the grouped kernels' three forward, three again under remat, three
    # transposed and three dw
    assert compiled.as_text().count("tpu_custom_call") == 2 + 12


# what the step program's trace left in `dispatch.taken()`, a case each:
# pytest-xdist hands files out by their number of cases, largest first, and a
# file of five cases round a compile of minutes would start last and end the
# run alone (PERF.md section 7, PR 49)
TAKEN = {
    "flash_attention.plan": lambda plans: [
        p.split(",dead")[1] for p in plans] == [
            "6/6%,rope_in_kernel,operands_bshd,heads1x128"],
    "grouped_matmul.plan": lambda plans: list(plans) == [
        "tile256x2048,rows12288,groups16"],
    "flash_attention": lambda paths: set(paths) == {"pallas"},
    "grouped_matmul": lambda paths: set(paths) == {"pallas"},
    "cca_moe.mix": lambda plans: list(plans) == [
        "taps2+2,heads8over2,latent1024+256,vshift,l2tau,xla"],
    "cca_moe.rope": lambda plans: list(plans) == [
        "hybrid:in_kernel64of128_columns_reordered_at_use_identity_tail"],
}


@pytest.mark.parametrize("key", sorted(TAKEN))
def test_cell_cca_moe_trace_left_its_plans_and_paths(step_program, key):
    assert TAKEN[key](step_program[1][key]), step_program[1][key]


def test_cell_cca_moe_grouped_kernels_are_found_by_their_names(step_program):
    """The experts are square, so benchmark/moe_faces.py's shapes cannot
    tell the forward grouped matmul from the transposed one; the `.cca`
    readers' patterns (benchmark/cca_faces.py) tell them by the kernel's
    name in `op_name`, and each finds its calls and no other's."""
    from benchmark import cca_faces, moe_faces

    compiled, _, _ = step_program
    names = _kernel_op_names(compiled)
    assert len(names) == 14
    found = {k: [n for n in names if re.search(getattr(cca_faces, k), n)]
             for k in ("GROUPED_FORWARD", "GROUPED_TRANSPOSED", "GROUPED_DW")}
    assert [len(found[k]) for k in found] == [6, 3, 3]
    assert all("/moe.experts/" in n for k in found for n in found[k])
    assert sum("rematted_computation" in n
               for n in found["GROUPED_FORWARD"]) == 3
    assert not set(found["GROUPED_FORWARD"]) & set(
        found["GROUPED_TRANSPOSED"] + found["GROUPED_DW"])
    flash = [n for n in names if "/attn.full/" in n]
    assert sorted(n.rsplit("/", 2)[1] for n in flash) == ["flash_bwd",
                                                          "flash_fwd"]
    # what shapes alone see: forward and transposed are one face here
    calls = _calls_as_traced(compiled)
    assert sum(bool(re.search(moe_faces.GROUPED_FORWARD, l))
               for l in calls) == 9
    assert not any(re.search(moe_faces.GROUPED_TRANSPOSED, l) for l in calls)


def test_cell_cca_moe_flash_forward_keeps_the_face_its_reader_finds(
        step_program):
    """`flash_fwd_roofline.cca` finds the forward by benchmark/
    swa_moe_faces.py's causal face: one call of the fourteen, with 8 heads
    of 128 over 8192 positions."""
    from benchmark import swa_moe_faces

    compiled, _, _ = step_program
    calls = _calls_as_traced(compiled)
    forward = [l for l in calls
               if re.search(swa_moe_faces.FORWARD_FULL, l)]
    assert len(forward) == 1
    assert "bf16[1,8192,1024]" in forward[0] \
        and "f32[8,8,8192]" in forward[0]
    assert not any(re.search(swa_moe_faces.FORWARD_WINDOWED, l)
                   for l in calls)
    assert sum(bool(re.search(swa_moe_faces.BACKWARD, l))
               for l in calls) == 1


def test_cell_cca_moe_scopes_are_where_the_readers_look(step_program):
    """`cca_mix_ms.cca` and `router_ms.cca` find their operations by
    `op_name`: `attn.mix` lies INSIDE `attn.full` in the forward, remat's
    forward and the backward; the router under `moe.route`; every kernel
    and every matmul keeps a scope of the vocabulary."""
    from ray_tpu.models import common

    compiled, _, _ = step_program
    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    mix = [n for n in names if common.ATTN_MIX in n]
    outside = [n for n in mix
               if f"{common.ATTN_FULL}/{common.ATTN_MIX}/" not in n]
    assert mix and not outside, outside[:5]
    assert any("rematted_computation" in n for n in mix)
    assert any(n.startswith("jit(_step_fn)/transpose(jvp())") for n in mix)
    route = [n for n in names if f"/{common.MOE_ROUTE}/" in n]
    assert any("dot_general" in n for n in route)
    assert any("erf" in n for n in route)       # the exact gelu
    scope = re.compile(r"(?<![\w.])(" + "|".join(
        re.escape(s) for s in common.SCOPES) + r")(?![\w.])")
    assert all(scope.search(n) for n in _kernel_op_names(compiled))
