"""AOT-compile the `train-ssd-moe-d9` cell's step program for a described
v5e (Nemotron-3-Nano-30B-A3B's widths, its first nine layers, 8 of 128
experts, an eighth of the vocabulary, 3 x 8192 tokens): its bytes, its
kernels' plans, and what the cell's readers find it by.

No chip is attached: the TPU compiler installed here compiles for a
topology that is described (v5e:2x2).  A compile that passes is not a chip
run.  tests/aot.py says how, and holds what the files of this name share.
"""

import re

import pytest

from aot import (_chip_bytes, _kernel_op_names, _scope_pattern,
                 hlo_is_as_recorded)

CONFIG = "nemotron-3-nano-30b-a3b-train-d9e8.json"

# `_step_fn`'s static arguments: the ladder's FIRST rung, which is the one the
# chip takes (the flash out and lse and the recurrence's y and first states
# kept)
STEP_STATIC = {"keep": True}


# sha256 of the step program's optimised HLO, `aot._metadata_stripped`:
# `aot.hlo_is_as_recorded` has the rule (a change that means to move the
# program replaces the digest and says so).  PR 49 MEANT TO: the chain round the
# recurrence is ops/mixer_chain.py's four kernels (PR 48's tree read
# 7dffeb93..).  PR 50 MEANT TO: the grouped kernels' forward / transposed
# grid walks a column block's row tiles before the next column block and
# both faces' matrices are ONE block (PR 49's tree read ec413a9a..).
PARENT_HLO_SHA256 = (
    "d67ca952facacb4528e16a1ced8b5b534b4f43c511fe412601d1e5c9ef458aea")


def test_cell_ssd_moe_optimised_hlo_is_as_this_pr_compiled_it(step_program):
    hlo_is_as_recorded(step_program[0].as_text(), PARENT_HLO_SHA256)


def test_cell_ssd_moe_step_program_fits_a_v5e(step_program):
    """The cell's whole step program (four mamba layers, four expert layers
    of 8 held experts of 1856, one attention layer, an eighth of the untied
    vocabulary, 3 x 8192 tokens, fused CE, bfloat16 moments) by AOT
    memory_analysis: under 15.75 GiB at the configuration's rows with room
    for what stands beside it, and over 11 (the state is 8.7)."""
    compiled, taken, tr, _ = step_program
    assert tr["batch_rows"] == 3 and tr["sequence_length"] == 8192
    total = _chip_bytes(compiled)
    assert 11.0 * 2 ** 30 < total < 14.5 * 2 ** 30, total / 2 ** 30
    plan, = taken["flash_attention.plan"]
    assert plan.endswith(",operands_bshd,heads1x128") \
        and "rope_in_kernel" not in plan


# what the step program's trace left in `dispatch.taken()`; the grouped
# plans are both sides of the buffer's `lax.cond`, the up face then the down
PLANS = {
    "ssd_scan.plan": ["chunk128,heads64over8,p64,n128,state_f32,bwd_pallas,"
                      "passes2.625+5.375"],
    "grouped_matmul.plan": [
        "tile256x1856,rows38912,groups8,n1856_whole",
        "tile256x2688,rows38912,groups8,k1856_whole",
        "tile256x1856,rows149504,groups8,n1856_whole",
        "tile256x2688,rows149504,groups8,k1856_whole"],
    "ssd_moe.experts": ["relu2,ungated,k6of128,held8"],
}


@pytest.mark.parametrize("key", sorted(PLANS))
def test_cell_ssd_moe_kernels_plans(step_program, key):
    assert list(step_program[1][key]) == PLANS[key]


@pytest.mark.parametrize("op", ["flash_attention", "grouped_matmul",
                                "routed_experts", "ssd_chain", "ssd_scan"])
def test_cell_ssd_moe_takes_every_kernel(step_program, op):
    """Each op of the cell went down its Pallas path; `ssd_chain` is each of
    ops/mixer_chain.py's two passes round the recurrence (PR 49)."""
    assert set(step_program[1][op]) == {"pallas"}


def _found(compiled):
    """name -> the kernel calls' `op_name`s that the name's pattern finds:
    the `.ssd` readers' (benchmark/ssd_faces.py) and the chain's four."""
    from benchmark import cca_faces, ssd_faces

    names = _kernel_op_names(compiled)
    return names, {k: [n for n in names if re.search(v, n)] for k, v in (
        ("ssd_fwd", ssd_faces.SSD_FORWARD), ("ssd_bwd", ssd_faces.SSD_BACKWARD),
        ("forward", ssd_faces.GROUPED_FORWARD),
        ("transposed", cca_faces.GROUPED_TRANSPOSED),
        ("dw", cca_faces.GROUPED_DW),
        *((k, rf"/{k}(?:/|$)") for k in (
            "ssd_chain_fwd", "ssd_chain_bwd", "gated_norm_fwd",
            "gated_norm_bwd")))}


# name -> (calls a step, of them under remat, the scope they lie in, a scope
# they must not lie in).  A mamba layer: ONE forward of the recurrence (its
# y and first states kept on this rung: remat runs no second one) and one
# backward; the chain's two passes round it (ops/mixer_chain.py, PR 49),
# each forward once in the pass and once under remat, each backward once,
# all under `ssm.chain`, where `mixer_chain_ms.ssd` reads them and
# `ssd_share.ssd`'s patterns do not; an expert layer: the two faces forward,
# again under remat, transposed and dw, on each side of the buffer's
# conditional.
KERNELS = {
    "ssd_fwd": (4, 0, "/ssm/", "ssm.chain"),
    "ssd_bwd": (4, 0, "/ssm/", "ssm.chain"),
    "ssd_chain_fwd": (8, 4, "/ssm/ssm.chain/", None),
    "ssd_chain_bwd": (4, 0, "/ssm/ssm.chain/", None),
    "gated_norm_fwd": (8, 4, "/ssm/ssm.chain/", None),
    "gated_norm_bwd": (4, 0, "/ssm/ssm.chain/", None),
    "forward": (4 * 2 * 2 * 2, 4 * 2 * 2, "/moe.experts/", None),
    "transposed": (4 * 2 * 2, 0, "/moe.experts/", None),
    "dw": (4 * 2 * 2, 0, "/moe.experts/", None),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_cell_ssd_moe_kernels_are_found_by_their_names(step_program, name):
    """A kernel's name in `op_name` finds its calls and no other's."""
    calls, rematted, scope, outside = KERNELS[name]
    found = _found(step_program[0])[1][name]
    assert len(found) == calls
    assert sum("rematted_computation" in n for n in found) == rematted
    assert all(scope in n and (outside is None or outside not in n)
               for n in found)


def test_cell_ssd_moe_every_kernel_call_has_one_name(step_program):
    """No call is found by two names, and beside the named ones the program
    holds the flash pair and the movers by the token alone."""
    names, found = _found(step_program[0])
    assert set(found) == set(KERNELS)
    assert len({n for v in found.values() for n in v}) \
        == sum(len(set(v)) for v in found.values())
    flash = [n for n in names if "/attn.full/" in n]
    assert sorted(n.rsplit("/", 2)[1] for n in flash) == ["flash_bwd",
                                                          "flash_fwd"]
    movers = [n for n in names if "gather" in n.rsplit("/", 2)[1]]
    assert len(names) == sum(map(len, found.values())) + 2 + len(movers)


def test_cell_ssd_moe_scopes_are_where_the_readers_look(step_program):
    """`mixer_chain_ms.ssd` finds its operations by `op_name`: `ssm.chain`
    lies INSIDE `ssm` in the forward, remat's forward and the backward, and
    holds exactly the four chain kernels' names and no matmul; every kernel
    and every matmul keeps a scope of the vocabulary."""
    from ray_tpu.models import common

    compiled = step_program[0]
    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    chain = [n for n in names if common.SSM_CHAIN in n]
    outside = [n for n in chain
               if f"{common.SSM}/{common.SSM_CHAIN}/" not in n]
    assert chain and not outside, outside[:5]
    assert any("rematted_computation" in n for n in chain)
    assert any(n.startswith("jit(_step_fn)/transpose(jvp())") for n in chain)
    assert not any("dot_general" in n for n in chain)
    assert {n.rsplit("/", 2)[1] for n in _kernel_op_names(compiled)
            if common.SSM_CHAIN in n} == {
        k for k, v in KERNELS.items() if "ssm.chain" in v[2]}
    scope = _scope_pattern()
    assert all(scope.search(n) for n in _kernel_op_names(compiled))
    assert all(scope.search(n) for n in names if "dot_general" in n)
