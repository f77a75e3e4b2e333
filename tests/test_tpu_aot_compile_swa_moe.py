"""AOT-compile the `train-swa-moe-d5` cell for a described v5e (the windowed
/ full GQA, routed-expert cell: a window WITH rope at head size 128, 72 and
48 heads, 8 experts of 3072 <-> 1024, 1 x 8192 tokens): its flash and
grouped-matmul calls at the cell's widths, the whole step program's bytes
and plans, its digest and its scopes.

tests/aot.py says what such a compile is and is not, and holds what the
files of this name share.
"""

import re

import jax
import jax.numpy as jnp

from aot import (_chip_bytes, _custom_calls_as_traced, _custom_calls_of,
                 _grouped_calls, every_matmul_and_kernel_is_scoped, face,
                 hlo_is_as_recorded, on_tpu)
from benchmark import moe_faces
from ray_tpu.ops import attention

CONFIG = "laguna-s-2.1-train-d5e8.json"
SWA_SEQ, SWA_HELD, SWA_TOP_K = 8192, 8, 10


def test_cell_swa_moe_flash_calls_compile_and_keep_the_faces_readers_find(
        one_chip, monkeypatch):
    """The sliding layers' call (72 heads, window 512, rope over the head)
    and the full layers' (48 heads, the triangle, the half rope as tables
    with an identity tail) at 1 x 8192 x 128: forward and backward compile
    (the roped forward asks 40 MiB of VMEM at this length), the windowed
    forward is found by swa_fwd_roofline.swamoe alone, the full one by
    flash_fwd_roofline.swamoe alone, the one-call backward by neither and
    by attention_share.swamoe's third pattern; the plans say how each call
    ropes."""
    from benchmark import swa_moe_faces as faces

    on_tpu(monkeypatch)
    monkeypatch.setattr(attention.dispatch, "_taken", {})
    swa = face("laguna_swa_moe", "swa_forward")
    full = face("laguna_swa_moe", "flash_forward")
    assert (swa, full) == (faces.FORWARD_WINDOWED, faces.FORWARD_FULL)
    assert face("laguna_swa_moe", "attention_all") == (
        faces.FORWARD_WINDOWED, faces.FORWARD_FULL, faces.BACKWARD)
    table = jax.ShapeDtypeStruct((1, SWA_SEQ, 64), jnp.float32,
                                 sharding=one_chip)
    for heads, window, mine, other in ((72, 512, swa, full),
                                       (48, None, full, swa)):
        x = jax.ShapeDtypeStruct((1, SWA_SEQ, heads, 128), jnp.bfloat16,
                                 sharding=one_chip)

        def attend(q, k, v, cos, sin, window=window):
            return attention.flash_attention(q, k, v, window=window,
                                             rope=(cos, sin))

        def loss(q, k, v, cos, sin):
            return attend(q, k, v, cos, sin).astype(jnp.float32).sum()

        calls = _custom_calls_as_traced(attend, x, x, x, table, table)
        assert len(calls) == 1 and re.search(mine, calls[0]), calls
        assert not re.search(other, calls[0])
        assert not re.search(faces.BACKWARD, calls[0])
        assert (f"(bf16[1,8192,{heads * 128}], f32[{heads},8,8192])"
                in calls[0])
        calls = _custom_calls_as_traced(jax.grad(loss, argnums=(0, 1, 2)),
                                        x, x, x, table, table)
        assert len(calls) == 2          # forward, backward
        assert sum(bool(re.search(mine, l)) for l in calls) == 1
        assert sum(bool(re.search(faces.BACKWARD, l)) for l in calls) == 1
        assert not any(re.search(other, l) for l in calls)
    assert sorted(attention.dispatch.taken()["flash_attention.plan"]) == [
        "fwd2048x512,bwd512x2048,dq_in_pass,dq_over4tiles,scale_per_score,"
        "dead6/6%,rope_in_kernel,operands_bshd,heads1x128",
        "fwd512x512,bwd512x512,dq_in_pass,dq_over16tiles,scale_per_score,"
        "dead50/50%,window512,visited12.1%,rope_in_kernel,operands_bshd,"
        "heads1x128"]


def test_cell_swa_moe_grouped_matmul_kernels_keep_their_faces(
        one_chip, monkeypatch):
    """Forward, transposed (dx) and dw at this cell's widths (3072 <-> 1024,
    8 groups) and both of its buffer sizes (the usual 4 x 2,560 rows and
    the bound of 8 x 8,192): each custom-call is found by exactly one of
    benchmark/moe_faces.py's patterns, which the `.swamoe` grouped readers
    share with the `.moe` ones."""
    from ray_tpu.ops import grouped_matmul as gm

    on_tpu(monkeypatch, gm)
    patterns = {"forward": moe_faces.GROUPED_FORWARD,
                "transposed": moe_faces.GROUPED_TRANSPOSED,
                "dw": moe_faces.GROUPED_DW}
    assert face("laguna_swa_moe", "grouped_forward") == patterns["forward"]
    assert face("laguna_swa_moe", "grouped_all") == tuple(patterns.values())

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def kinds(calls):
        found = [[k for k, p in patterns.items() if re.search(p, l)]
                 for l in calls]
        assert all(len(f) == 1 for f in found), (calls, found)
        return sorted(f[0] for f in found)

    even = SWA_SEQ * SWA_TOP_K * SWA_HELD // 256
    for buffer in (4 * even, SWA_SEQ * min(SWA_TOP_K, SWA_HELD)):
        rows = gm.layout_rows(buffer, SWA_HELD)
        for k, n in ((3072, 1024), (1024, 3072)):
            def product(x, w, sizes, rows=rows):
                return gm.grouped_matmul(x, w, gm.group_layout(sizes, rows))

            shapes = (sds((rows, k)), sds((SWA_HELD, k, n)),
                      sds((SWA_HELD,), jnp.int32))
            assert kinds(_custom_calls_as_traced(product, *shapes)) \
                == ["forward"]
            calls = _custom_calls_as_traced(
                jax.grad(lambda *a: product(*a).astype(jnp.float32).sum(),
                         argnums=(0, 1)), *shapes)
            assert kinds(calls) == ["dw", "transposed"], calls


def test_cell_swa_moe_step_program_fits_a_v5e(step_program):
    """The cell's whole step program (a full + dense layer, three sliding
    and one full expert layer, 8 of 256 experts, an eighth of the
    vocabulary, 1 x 8192 tokens, full remat, fused CE, bfloat16 moments) by
    AOT memory_analysis: under 15.75 GiB at the configuration's rows."""
    compiled, taken, tr, _ = step_program
    assert tr["batch_rows"] == 1 and tr["sequence_length"] == SWA_SEQ
    total = _chip_bytes(compiled)
    assert 13.0 * 2 ** 30 < total < 15.75 * 2 ** 30, total / 2 ** 30
    # Three segments, each flash forward, forward again under remat and
    # backward (3); the two with experts also the grouped kernels, twelve
    # at each of the layer's two buffer sizes (a cond's two sides) and the
    # two movers by the token beside them (PR 45).
    assert compiled.as_text().count("tpu_custom_call") == (
        3 * 3 + 2 * 2 * (12 + 2))
    assert _grouped_calls(_custom_calls_of(compiled)) == 2 * 2 * 12
    assert set(taken["routed_experts"]) == {"pallas"}
    assert all(p.startswith("rows_by_index,slots81920,buffer")
               for p in taken["routed_experts.plan"])
    # both faces' matrices are ONE block (PR 50: 6 MiB of the 16 a block
    # may take; two blocks of 512 and 1536 columns before), at the usual
    # buffer and at the bound's
    assert sorted(taken["grouped_matmul.plan"]) == [
        "tile256x1024,rows12288,groups8", "tile256x1024,rows67584,groups8",
        "tile256x3072,rows12288,groups8", "tile256x3072,rows67584,groups8"]
    assert sorted(p.split(",dead")[1] for p in
                  taken["flash_attention.plan"]) == [
        "50/50%,window512,visited12.1%,rope_in_kernel,operands_bshd,"
        "heads1x128", "6/6%,rope_in_kernel,operands_bshd,heads1x128"]
    assert list(taken["swa_moe.rope"]) == [
        "full_attention:in_kernel64of128_columns_reordered_at_use_identity_"
        "tail,sliding_attention:in_kernel128of128"]


# sha256 of the step program's optimised HLO, `aot._metadata_stripped`
# (`aot.hlo_is_as_recorded` has the rule).  PR 45 MEANT TO move it (the
# movers by the token are a kernel, ops/row_gather.py), and PR 50, whose
# tree's this is: ops/grouped_matmul.py's forward / transposed grid walks a
# column block's row tiles before the next column block, and this cell's
# matrices are one block where they were two (PR 49's tree read 9846286b..).
PARENT_HLO_SHA256 = (
    "f2e033300b79a9054556a89eb396fc3ad498458c8b1ff89950c36042f0fb56f1")


def test_the_scopes_left_the_optimised_hlo_as_the_parent_compiled_it(
        step_program):
    hlo_is_as_recorded(step_program[0].as_text(), PARENT_HLO_SHA256)


def test_every_matmul_and_every_kernel_carries_a_scope_of_the_vocabulary(
        step_program):
    every_matmul_and_kernel_is_scoped(step_program[0].as_text(),
                                      whole_step=True)
