"""AOT-compile the `train-cca-moe-d4` cell's step program for a described
v5e (ZAYA1-8B's widths, four layers, all sixteen experts, 1 x 8192 tokens):
its bytes, its kernels' plans, and what the cell's readers find it by.

No chip is attached: the TPU compiler installed here compiles for a
topology that is described (v5e:2x2).  A compile that passes is not a chip
run.  tests/aot.py says how, and holds what the files of this name share.
"""

import re

import pytest

from aot import (_chip_bytes, _custom_calls_of, _kernel_op_names,
                 _scope_pattern, hlo_is_as_recorded)

CONFIG = "zaya1-8b-train-d4.json"

# `_step_fn`'s static arguments: the ladder's FIRST rung, each layer's flash
# out and lse kept, which is the one the chip takes
STEP_STATIC = {"keep": True}


# sha256 of the step program's optimised HLO, `aot._metadata_stripped`, as PR
# 46's tree (40fa1e4) compiled it: `aot.hlo_is_as_recorded` has the rule (a
# change that means to move the program replaces the digest and says so).
# PR 50 MEANT TO:
# the grouped kernels' forward / transposed grid walks a column block's row
# tiles before the next column block and the 2048 x 2048 matrix is ONE block
# (PR 46's tree read 2d09fc2a..).
PARENT_HLO_SHA256 = (
    "80b748b99e62eb2171c103fe147284e3439f32ea2a6216d5e70600fbff9fe333")


def test_cell_cca_moe_optimised_hlo_is_as_the_parent_compiled_it(
        step_program):
    hlo_is_as_recorded(step_program[0].as_text(), PARENT_HLO_SHA256)


def test_cell_cca_moe_step_program_fits_a_v5e(step_program):
    """The cell's whole step program (four layers of compressed
    convolutional attention and sixteen held experts of 2048, an eighth of
    the tied vocabulary, 1 x 8192 tokens, out and lse kept across remat,
    fused CE, bfloat16 moments) by AOT memory_analysis: under 15.75 GiB at
    the configuration's rows, and over 13 (the state alone is 11.7)."""
    compiled, _, tr, _ = step_program
    assert tr["batch_rows"] == 1 and tr["sequence_length"] == 8192
    total = _chip_bytes(compiled)
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

    compiled = step_program[0]
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
    calls = _custom_calls_of(compiled)
    assert sum(bool(re.search(moe_faces.GROUPED_FORWARD, l))
               for l in calls) == 9
    assert not any(re.search(moe_faces.GROUPED_TRANSPOSED, l) for l in calls)


def test_cell_cca_moe_flash_forward_keeps_the_face_its_reader_finds(
        step_program):
    """`flash_fwd_roofline.cca` finds the forward by benchmark/
    swa_moe_faces.py's causal face: one call of the fourteen, with 8 heads
    of 128 over 8192 positions."""
    from benchmark import swa_moe_faces

    compiled = step_program[0]
    calls = _custom_calls_of(compiled)
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

    compiled = step_program[0]
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
    scope = _scope_pattern()
    assert all(scope.search(n) for n in _kernel_op_names(compiled))
