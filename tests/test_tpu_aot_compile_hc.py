"""AOT-compile the `train-mhc-mla-moe-d5` cell's step program for a described
v5e (Xing4.0-29B-A4B's widths, one dense + four expert layers, 8 of 64
experts, an eighth of the vocabulary, 1 x 8192 tokens; the prediction block
off this chip) on the rung the chip takes, the ladder's LOWEST: its bytes,
its kernels' plans, and what the cell's readers find it by.

No chip is attached: the TPU compiler installed here compiles for a
topology that is described (v5e:2x2).  A compile that passes is not a chip
run.  tests/aot.py says how, and holds what the files of this name share.
Its assertions are spelt out as two dozen cases round ONE compile, so that
`--dist loadfile` (files by their number of tests, largest first) starts the
file early (PERF.md section 7).
"""

import re

import pytest

from aot import (_chip_bytes, _kernel_op_names, _scope_pattern, config_doc,
                 hlo_is_as_recorded)

CONFIG = "xing4.0-29b-a4b-train-d5e8.json"

# `_step_fn`'s static arguments: the ladder's LOWEST rung, nothing kept across
# a LAYER's checkpoint (the first rung's program, out and lse of every layer
# held from the forward to the backward, reads 16.44 GiB, over).  Inside a
# layer's backward attention's own checkpoint keeps the flash kernel's out and
# lse, one layer's 65 MiB at a time (`latent_moe._layer`, PR 55)
STEP_STATIC = {"keep": False}


# sha256 of the step program's optimised HLO, `aot._metadata_stripped`, as PR
# 55's tree compiled it: `aot.hlo_is_as_recorded` has the rule (a change that
# means to move the program replaces the digest and says so).  PR 55 MEANT to
# move it: attention's inner checkpoint keeps `attn_out` and `attn_lse`, so a
# layer's backward runs no third flash forward (PR 52's digest was
# 2955a191fd5b3381f5cf7c59d09625d964ff12b4c45191955f287a6bae6c9eaf).
PARENT_HLO_SHA256 = (
    "7434dd6485cc8065b6636ffa9aba3329ddc96ed221b03a20fd1fd85cc04f9fba")


def test_cell_mhc_optimised_hlo_is_as_this_pr_compiled_it(step_program):
    hlo_is_as_recorded(step_program[0].as_text(), PARENT_HLO_SHA256)


def test_cell_mhc_holds_the_configurations_parameters(step_program):
    """759,403,795: the count `train_why` and benchmark/arith_hc_moe.py
    give."""
    from benchmark import arith_hc_moe

    model = config_doc(CONFIG)["model"]
    assert step_program[3] == arith_hc_moe.param_count(model) == 759_403_795


def test_cell_mhc_step_program_fits_a_v5e(step_program):
    """The whole step program (a dense layer, four expert layers of 8 held
    experts of 1024, an eighth of the untied vocabulary, a stream of four
    lanes, 1 x 8192 tokens, fused CE, bfloat16 moments) by AOT
    memory_analysis: 15.295 GiB (PR 55; 15.219 before attention's inner
    checkpoint kept out and lse), under 15.75 with 0.4 of room for what
    stands beside it on the chip (0.3), and over 13 (the state is 9.9 at 14
    B)."""
    compiled, _, tr, _ = step_program
    assert tr["batch_rows"] == 1 and tr["sequence_length"] == 8192
    total = _chip_bytes(compiled)
    assert 13.0 * 2 ** 30 < total < 15.35 * 2 ** 30, total / 2 ** 30


# what the step program's trace left in `dispatch.taken()`; the grouped
# plans are both sides of the buffer's `lax.cond`
PLANS = {
    "flash_attention.plan": [
        "fwd2048x512,bwd512x2048,dq_in_pass,dq_over4tiles,scale_per_score,"
        "dead6/6%,dqk192,dv128,latent_parts,rope_in_kernel64of192"],
    "grouped_matmul.plan": [
        "tile256x1024,rows10240,groups8", "tile256x3584,rows10240,groups8",
        "tile256x1024,rows34816,groups8", "tile256x3584,rows34816,groups8"],
    "routed_experts.plan": [
        "rows_by_index,slots32768,buffer10240,entries<=8192",
        "rows_by_index,slots32768,buffer34816,entries<=32768"],
}


@pytest.mark.parametrize("key", sorted(PLANS))
def test_cell_mhc_kernels_plans(step_program, key):
    assert list(step_program[1][key]) == PLANS[key]


@pytest.mark.parametrize("op", ["flash_attention", "grouped_matmul",
                                "routed_experts", "hyper_connection"])
def test_cell_mhc_takes_every_kernel(step_program, op):
    """Each op of the cell went down its Pallas path (`must_take_pallas`)."""
    assert set(step_program[1][op]) == {"pallas"}


def test_cell_mhc_attentions_own_checkpoint_keeps_out_and_lse(step_program):
    """What `latent_moe._layer` recorded as it was traced: the mechanism
    engaged in both bodies of a layer (the dense layer, the scan's)."""
    assert step_program[1]["latent_moe.attention_checkpoint"] == {
        "kept:attn_out,attn_lse": 2}


def _found(compiled):
    """name -> the kernel calls' `op_name`s that the cell's faces find
    (benchmark/hc_faces.py)."""
    from benchmark import cca_faces, hc_faces

    names = _kernel_op_names(compiled)
    return names, {k: [n for n in names if re.search(v, n)] for k, v in (
        ("hc_pre_fwd", hc_faces.HC_PRE_FORWARD),
        ("hc_post_fwd", hc_faces.HC_POST_FORWARD),
        ("hc_pre_bwd", hc_faces.HC_PRE_BACKWARD),
        ("hc_post_bwd", hc_faces.HC_POST_BACKWARD),
        ("flash_fwd", hc_faces.FLASH_FORWARD),
        ("flash_bwd", r"/flash_bwd(?:/|$)"),
        ("forward", hc_faces.GROUPED_FORWARD),
        ("transposed", cca_faces.GROUPED_TRANSPOSED),
        ("dw", cca_faces.GROUPED_DW))}


# name -> (call sites in the program, of them under remat, the scope they lie
# in).  The program holds TWO bodies of a layer: the dense layer, the scan's
# body of the four expert layers.  A body: `hc_pre` round both sublayers
# forward and again under remat, `hc_post` likewise less remat's last
# (nothing of the backward reads the layer's output), each backward once; the
# collapse is a forward and a backward `hc_pre`.  The flash forward runs
# TWICE a body on this rung: forward and the layer's remat.  Attention's own
# inner checkpoint (`latent_moe._layer`) keeps the kernel's out and lse from
# the layer's remat to attention's backward, which makes the projections a
# third time and calls no forward kernel (PR 55; three calls a body before).
# The dense body's outer checkpoint (`latent_moe._stream`) adds no call: all
# it makes again is the embedding's copies.  The expert body: the three
# grouped faces forward, under remat and in the backward's own checkpoint, on
# each side of the buffer's conditional; transposed and dw.
KERNELS = {
    "hc_pre_fwd": (2 * 4 + 1, 2 * 2, "resid.mix"),
    "hc_post_fwd": (2 * 3, 2, "resid.mix"),
    "hc_pre_bwd": (2 * 2 + 1, 0, "resid.mix"),
    "hc_post_bwd": (2 * 2, 0, "resid.mix"),
    "flash_fwd": (2 * 2, 2 * 1, "attn.full"),
    "flash_bwd": (2, 0, "attn.full"),
    "forward": (18, 12, "moe.experts"),
    "transposed": (6, 0, "moe.experts"),
    "dw": (6, 0, "moe.experts"),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_cell_mhc_kernels_are_found_by_their_names(step_program, name):
    """A kernel's name in `op_name` finds its calls and no other's."""
    calls, rematted, scope = KERNELS[name]
    found = _found(step_program[0])[1][name]
    assert len(found) == calls
    assert sum("rematted_computation" in n for n in found) == rematted
    inside = re.compile(r"(?<![\w.])" + re.escape(scope) + r"(?![\w.])")
    assert all(inside.search(n) for n in found)


def test_cell_mhc_every_kernel_call_has_one_name(step_program):
    """No call is found by two names, and beside the named ones the program
    holds the movers by the token alone."""
    names, found = _found(step_program[0])
    assert set(found) == set(KERNELS)
    assert len({n for v in found.values() for n in v}) \
        == sum(len(set(v)) for v in found.values())
    movers = [n for n in names if "gather" in n.rsplit("/", 2)[1]]
    assert len(names) == sum(map(len, found.values())) + len(movers)


def test_cell_mhc_lanes_lie_outside_every_sublayers_scope(step_program):
    """`residual_mix_ms` finds its operations by `op_name`: `resid.mix` is
    there in the forward, remat's forward and the backward, holds the four
    kernels and no matmul, and none of its operations lies under a
    sublayer's scope (the tiling puts them in `unscoped`)."""
    from benchmark import part_lib
    from ray_tpu.models import common

    names = re.findall(r'op_name="([^"]*)"', step_program[0].as_text())
    mix = [n for n in names if common.RESID_MIX in n]
    assert mix and any("rematted_computation" in n for n in mix)
    assert any(n.startswith("jit(_step_fn)/transpose(jvp(") for n in mix)
    assert not any("dot_general" in n for n in mix)
    assert {part_lib.scope_of(n) for n in mix} == {None}
    assert {n.rsplit("/", 2)[1] for n in _kernel_op_names(step_program[0])
            if common.RESID_MIX in n} == {
        k for k, v in KERNELS.items() if v[2] == "resid.mix"}


def test_cell_mhc_every_kernel_and_matmul_keeps_a_scope(step_program):
    from ray_tpu.models import common

    compiled = step_program[0]
    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    scope = _scope_pattern(common.RESID_MIX)
    assert all(scope.search(n) for n in _kernel_op_names(compiled))
    assert all(scope.search(n) for n in names if "dot_general" in n)
