"""AOT-compile the `train-moe-mla-d6` cell for a described v5e (PR 34: latent
attention's 192 / 128 flash calls, 16 of 128 dropless routed experts, 2 x
8192 tokens): the latent flash calls and the grouped-matmul kernels at the
cell's widths, the movers by the token at every expert cell's sizes, the
whole step program's bytes and plans, its digest and its scopes.

tests/aot.py says what such a compile is and is not, and holds what the
files of this name share.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from aot import (_chip_bytes, _custom_calls_as_traced, _custom_calls_of,
                 _every_face, _grouped_calls,
                 every_matmul_and_kernel_is_scoped, face, hlo_is_as_recorded,
                 on_tpu)
from benchmark import moe_faces
from ray_tpu.ops import attention

CONFIG = "kanana-2-30b-a3b-train-d6e16.json"
MOE_ROWS, MOE_SEQ, MOE_HEADS = 2, 8192, 32
MOE_TOKENS, MOE_HELD, MOE_TOP_K = MOE_ROWS * MOE_SEQ, 16, 6


def test_cell_latent_flash_compiles_and_keeps_the_face_its_reader_finds(
        one_chip, monkeypatch):
    """Keys 192 wide, values 128, WHOLE operands at the cell's shapes (the
    cell itself has taken the parts since PR 35: the next test): ONE
    forward call that takes q, k as [2, 8192, 32 x 192] and v and gives out
    as [2, 8192, 32 x 128], two heads a program (384 and 256 lanes), one
    backward call with dq, dk and dv laid the same; nothing padded; the
    plan says both widths.  mla_fwd_roofline.moe's face, q bf16[bh, s, 192]
    second, is the parts' call's alone: this one no longer wears it."""
    on_tpu(monkeypatch)
    monkeypatch.setattr(attention.dispatch, "_taken", {})
    q = jax.ShapeDtypeStruct((MOE_ROWS, MOE_SEQ, MOE_HEADS, 192),
                             jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((MOE_ROWS, MOE_SEQ, MOE_HEADS, 128),
                             jnp.bfloat16, sharding=one_chip)
    mla = moe_faces.MLA_FORWARD
    assert face("deepseek_v3_mla_moe", "mla_forward") == mla
    sm_scale = 192 ** -0.5

    def attend(q, k, v):
        return attention.flash_attention(q, k, v, sm_scale=sm_scale)

    # forward and backward from ONE program (two heads of 192 / 128 a
    # program are the slowest kernels here for Mosaic to compile)
    calls = _custom_calls_as_traced(
        jax.grad(lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
                 argnums=(0, 1, 2)), q, q, v)
    forward = [l for l in calls if "= (bf16[2,8192,4096], f32[" in l]
    backward = [l for l in calls if l not in forward]
    assert len(forward) == 1 and len(backward) == 1, calls
    assert not re.search(mla, forward[0]), forward
    assert ("(bf16[2,8192,4096], f32[64,8,8192]) custom-call(s32[2] "
            in forward[0])
    assert re.search(r"custom-call\(s32\[2\] [^,]+, bf16\[2,8192,6144\] ",
                     forward[0]), forward[0]
    assert ("= (bf16[2,8192,6144], bf16[2,8192,6144], bf16[2,8192,4096]) "
            "custom-call(s32[2] ") in backward[0]
    # an equal-width call is not mistaken for it
    x = jax.ShapeDtypeStruct((1, MOE_SEQ, 40, 128), jnp.bfloat16,
                             sharding=one_chip)
    calls = _custom_calls_as_traced(
        lambda q, k, v: attention.flash_attention(q, k, v), x, x, x)
    assert len(calls) == 1 and not re.search(mla, calls[0])
    plans = list(attention.dispatch.taken()["flash_attention.plan"])
    assert ("fwd2048x512,bwd512x2048,dq_in_pass,dq_over4tiles,"
            "scale_per_score,dead6/6%,dqk192,dv128,operands_bshd,"
            "heads2x192") in plans
    assert ("fwd2048x512,bwd512x2048,dq_in_pass,dq_over4tiles,"
            "scale_per_score,dead6/6%,operands_bshd,heads1x128") in plans


def test_cell_latent_parts_compile_and_keep_the_face_its_reader_finds(
        one_chip, monkeypatch):
    """The cell's call since PR 35, `latent_flash_attention` at its shapes:
    q [2, 8192, 32, 192] un-roped, kv [2, 8192, 32, 256] as W_kvb lays it,
    ONE rotary key [2, 8192, 64].  Forward and ONE backward call compile
    for the v5e.  The forward keeps the face mla_fwd_roofline.moe finds (q
    bf16[64, 8192, 192] second, results 128 wide); kv, the rotary key and
    the tables go in as XLA lays them.  The backward, which no reader
    finds, gives dq [64, 8192, 192], [dk_nope | dv] laid as kv, and the
    rotary key's gradient a share a head.  The plan holds the parts' word
    behind the widths and nothing of how whole operands are taken; the
    dense, hybrid and whole-operand plans say that and not the parts'."""
    on_tpu(monkeypatch)
    monkeypatch.setattr(attention.dispatch, "_taken", {})

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = (sds((MOE_ROWS, MOE_SEQ, MOE_HEADS, 192)),
              sds((MOE_ROWS, MOE_SEQ, MOE_HEADS, 256)),
              sds((MOE_ROWS, MOE_SEQ, 64)),
              sds((MOE_ROWS, MOE_SEQ, 32), jnp.float32),
              sds((MOE_ROWS, MOE_SEQ, 32), jnp.float32))
    mla = moe_faces.MLA_FORWARD

    def attend(q, kv, k_pe, cos, sin):
        return attention.latent_flash_attention(q, kv, k_pe, (cos, sin),
                                                sm_scale=192 ** -0.5)

    calls = _custom_calls_as_traced(attend, *shapes)
    assert len(calls) == 1 and re.search(mla, calls[0]), calls
    assert "(bf16[64,8192,128], f32[64,8,8192]) custom-call(s32[2] " \
        in calls[0]
    operands = calls[0].split("custom-call(", 1)[1]
    assert re.match(
        r"s32\[2\] [^,]+, bf16\[64,8192,192\] [^,]+, bf16\[2,8192,8192\] "
        r"[^,]+, bf16\[2,8192,64\] [^,]+, f32\[2,8192,64\] [^,]+, "
        r"f32\[2,8192,64\] ", operands), operands
    calls = _custom_calls_as_traced(
        jax.grad(lambda *a: attend(*a).astype(jnp.float32).sum(),
                 argnums=(0, 1, 2)), *shapes)
    backward = [l for l in calls if not re.search(mla, l)]
    assert len(calls) == 2 and len(backward) == 1, calls
    assert ("= (bf16[64,8192,192], bf16[2,8192,8192], bf16[64,8192,64]) "
            "custom-call(s32[2] ") in backward[0]
    assert "bf16[2,8192,4096] " in backward[0]      # do, as W_o's side has it
    parts_plan = ("fwd2048x512,bwd512x2048,dq_in_pass,dq_over4tiles,"
                  "scale_per_score,dead6/6%,dqk192,dv128,latent_parts,"
                  "rope_in_kernel64of192")
    assert list(attention.dispatch.taken()["flash_attention.plan"]) == [
        parts_plan]
    # the other cells' calls and the whole-operand 192 / 128 call: no word
    monkeypatch.setattr(attention.dispatch, "_taken", {})
    x = sds((5, 2048, 32, 64))
    t = sds((5, 2048, 32), jnp.float32)
    jax.jit(lambda q, c, s: attention.flash_attention(
        q, q, q, rope=(c, s))).lower(x, t, t)
    x = sds((1, MOE_SEQ, 40, 128))
    jax.jit(lambda q: attention.flash_attention(
        q, q, q, sm_scale=0.125)).lower(x)
    jax.jit(lambda q: attention.flash_attention(
        q, q, q, sm_scale=0.125, window=512)).lower(x)
    jax.jit(lambda q, v: attention.flash_attention(
        q, q, v, sm_scale=192 ** -0.5)).lower(shapes[0], sds(
            (MOE_ROWS, MOE_SEQ, MOE_HEADS, 128)))
    assert sorted(attention.dispatch.taken()["flash_attention.plan"]) == [
        "fwd2048x512,bwd512x2048,dq_in_pass,dq_over4tiles,scale_folded,"
        "dead6/6%,operands_bshd,heads1x128",
        "fwd2048x512,bwd512x2048,dq_in_pass,dq_over4tiles,scale_per_score,"
        "dead6/6%,dqk192,dv128,operands_bshd,heads2x192",
        "fwd2048x512,bwd512x2048,dq_in_pass,scale_folded,dead20/20%,"
        "rope_in_kernel,operands_bshd,heads2x64",
        "fwd512x512,bwd512x512,dq_in_pass,dq_over16tiles,scale_folded,"
        "dead50/50%,window512,visited12.1%,operands_bshd,heads1x128"]


def test_cell_grouped_matmul_kernels_compile_and_keep_their_faces(
        one_chip, monkeypatch):
    """Forward, transposed (dx) and dw at the cell's widths (2048 <-> 768,
    16 groups, the bound of 6 x 16,384 rows): each custom-call is found by
    exactly one of benchmark/moe_faces.py's patterns, which the grouped
    readers share."""
    from ray_tpu.ops import grouped_matmul as gm

    on_tpu(monkeypatch, gm)
    monkeypatch.setattr(gm.dispatch, "_taken", {})
    patterns = {"forward": moe_faces.GROUPED_FORWARD,
                "transposed": moe_faces.GROUPED_TRANSPOSED,
                "dw": moe_faces.GROUPED_DW}
    assert face("deepseek_v3_mla_moe", "grouped_forward") \
        == patterns["forward"]
    assert face("deepseek_v3_mla_moe", "grouped_all") == tuple(
        patterns.values())
    rows = gm.layout_rows(MOE_TOKENS * MOE_TOP_K, MOE_HELD)
    assert rows == 102_400

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def kinds(calls):
        found = [[k for k, p in patterns.items() if re.search(p, l)]
                 for l in calls]
        assert all(len(f) == 1 for f in found), (calls, found)
        return sorted(f[0] for f in found)

    for k, n in ((2048, 768), (768, 2048)):
        def product(x, w, sizes):
            return gm.grouped_matmul(x, w, gm.group_layout(sizes, rows))

        shapes = (sds((rows, k)), sds((MOE_HELD, k, n)),
                  sds((MOE_HELD,), jnp.int32))
        calls = _custom_calls_as_traced(product, *shapes)
        assert kinds(calls) == ["forward"], calls
        assert f"= bf16[{rows},{n}] custom-call(s32[400] " in calls[0]
        calls = _custom_calls_as_traced(
            jax.grad(lambda *a: product(*a).astype(jnp.float32).sum(),
                     argnums=(0, 1)), *shapes)
        assert kinds(calls) == ["dw", "transposed"], calls
    taken = gm.dispatch.taken()
    assert set(taken["grouped_matmul"]) == {"pallas"}
    assert sorted(taken["grouped_matmul.plan"]) == [
        "tile256x2048,rows102400,groups16", "tile256x768,rows102400,groups16"]


# cell -> tokens, k, width, the usual buffer's rows and the bound's
ROW_GATHER_CELLS = {
    "train-gdn-moe-d4": (24_576, 10, 2048, 69_632, 253_952),
    "train-moe-mla-d6": (16_384, 6, 2048, 28_672, 102_400),
    "train-swa-moe-d5": (8_192, 10, 3072, 12_288, 67_584),
    "train-cca-moe-d4": (8_192, 1, 2048, 12_288, 12_288),
}


@pytest.mark.parametrize("cell", sorted(ROW_GATHER_CELLS))
def test_cell_row_gather_kernel_compiles_and_wears_no_readers_face(
        cell, one_chip, monkeypatch):
    """ops/row_gather.py at each expert cell's sizes: the sum back (a token's
    k slots, weighted, from either buffer) and placing's transpose (the
    same, unweighted) compile for a v5e where k > 1, ONE custom call each
    that none of the readers' patterns finds; at k = 1 (a slot is a row) no
    kernel is made: XLA's gather stays."""
    from ray_tpu.ops import row_gather as rg

    on_tpu(monkeypatch, rg)
    tokens, k, h, usual, bound = ROW_GATHER_CELLS[cell]
    assert {"train-gdn-moe-d4": 245_760, "train-moe-mla-d6": 98_304,
            "train-swa-moe-d5": 81_920}.get(cell, tokens) == tokens * k

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    faces = _every_face()
    assert len(faces) == 4 + 3 + 3
    for buffer in sorted({usual, bound}):
        lists = (sds((buffer, h), jnp.bfloat16),
                 sds((tokens * k,), jnp.int32), sds((tokens,), jnp.int32))
        for weights in ((sds((tokens * k,), jnp.float32),), ()):
            calls = _custom_calls_as_traced(rg.gather_sum, *lists, *weights)
            assert len(calls) == (k > 1), calls
            for line in calls:
                assert f"= bf16[{tokens},{h}] custom-call(s32[" in line
                assert not [n for n, p in faces.items()
                            if re.search(p, line)], line
    assert rg.path(h, k) == ("pallas" if k > 1 else "xla")


def test_cell_latent_moe_step_program_fits_a_v5e(step_program):
    """The cell's whole step program (a dense and five expert layers, 16 of
    128 experts, an eighth of the vocabulary, 2 x 8192 tokens, full remat,
    fused CE, bfloat16 moments) by AOT memory_analysis: under 15.75 GiB at
    the configuration's rows."""
    compiled, taken, tr, _ = step_program
    assert tr["batch_rows"] == MOE_ROWS and tr["sequence_length"] == MOE_SEQ
    total = _chip_bytes(compiled)
    assert 13.0 * 2 ** 30 < total < 15.75 * 2 ** 30, total / 2 ** 30
    # The dense layer: flash forward, forward again under remat, backward
    # (3).  The five expert layers are ONE scanned body: those three and
    # the grouped kernels, three forward, three again for the backward,
    # three transposed and three dw (12), at each of the layer's two
    # buffer sizes (the usual and the full bound: a cond's two sides), and
    # the two movers by the token (PR 45: the weighted sum back, placing's
    # transpose; remat's second sum back feeds nothing and is not compiled).
    assert compiled.as_text().count("tpu_custom_call") == 3 + 3 + 2 * (12 + 2)
    assert _grouped_calls(_custom_calls_of(compiled)) == 2 * 12
    assert set(taken["routed_experts"]) == {"pallas"}
    assert sorted(taken["routed_experts.plan"]) == [
        "rows_by_index,slots98304,buffer102400,entries<=98304",
        "rows_by_index,slots98304,buffer28672,entries<=24576"]
    # and the attention calls are the ones that take latent attention's parts
    assert all(p.endswith(",dqk192,dv128,latent_parts,rope_in_kernel64of192")
               for p in taken["flash_attention.plan"])


# sha256 of the step program's optimised HLO, `aot._metadata_stripped`
# (`aot.hlo_is_as_recorded` has the rule).  PR 45 MEANT TO move it (the
# movers by the token are a kernel, ops/row_gather.py), and PR 50, whose
# tree's this is: ops/grouped_matmul.py's forward / transposed grid walks a
# column block's row tiles before the next column block; this cell's
# matrices were one block before and after, the kernel's two grid axes
# changed places and nothing else (PR 49's tree read a601fb23..).
PARENT_HLO_SHA256 = (
    "3ca4b7c9c283cef8b41c7806c1711cd6c60458da9f93049b76045b9bd299f8dd")


def test_the_scopes_left_the_optimised_hlo_as_the_parent_compiled_it(
        step_program):
    hlo_is_as_recorded(step_program[0].as_text(), PARENT_HLO_SHA256)


def test_every_matmul_and_every_kernel_carries_a_scope_of_the_vocabulary(
        step_program):
    every_matmul_and_kernel_is_scoped(step_program[0].as_text(),
                                      whole_step=True)
