"""AOT-compile the main path's Pallas kernels for a described v5e.

No chip is attached: the TPU compiler installed here compiles for a
topology that is described (v5e:2x2), at the widths of the 1.1 B GQA
model the repo serves and the 0.9 B model it trains.  What the chip's
compiler would refuse (tiling, VMEM, partitioning) it refuses here, at
no chip time.  A compile that passes is not a chip run.

This file holds the kernels compiled ALONE; a cell's whole step program
has a file of its own (tests/test_tpu_aot_compile_<cell>.py), the dense
layer's programs tests/test_tpu_aot_compile_dense_layer.py, and
tests/aot.py holds what they share (the described topology is
tests/conftest.py's `topo` and `one_chip`).
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from aot import _compiled_text, _custom_calls_as_traced, face, on_tpu
from ray_tpu.ops import attention, paged_attention

# 1.1 B GQA serving widths (chip_smoke.py).
B, H, KVH, D = 128, 16, 4, 128
PAGE, NUM_PAGES = 128, 320


@pytest.mark.parametrize("table_width", [2, 16])
def test_paged_gqa_decode_kernel_compiles_for_v5e(one_chip, table_width):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def decode(q, k_pages, v_pages, tables, lens):
        return paged_attention._paged_attention_pallas(
            q, k_pages, v_pages, tables, lens, D ** -0.5, interpret=False)

    text = _compiled_text(
        decode, sds((B, H, D), jnp.bfloat16),
        sds((NUM_PAGES, PAGE, KVH * D), jnp.bfloat16),
        sds((NUM_PAGES, PAGE, KVH * D), jnp.bfloat16),
        sds((B, table_width), jnp.int32), sds((B,), jnp.int32))
    assert "tpu_custom_call" in text


def test_write_token_rows_compiles_for_v5e(one_chip, monkeypatch):
    # The op picks interpret mode from the live backend (the CPU, here);
    # steer it in the test, not through an option of the program.
    monkeypatch.setattr(paged_attention.dispatch, "platform", lambda: "tpu")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = _compiled_text(
        paged_attention.write_token_rows,
        sds((NUM_PAGES, PAGE, KVH * D), jnp.bfloat16),
        sds((NUM_PAGES, PAGE, KVH * D), jnp.bfloat16),
        sds((B, KVH, D), jnp.bfloat16), sds((B, KVH, D), jnp.bfloat16),
        sds((B, 16), jnp.int32), sds((B,), jnp.int32))
    assert "tpu_custom_call" in text


def _flash(q, k, v):
    out, _ = attention.flash_attention_chunk(
        q, k, v, 0, 0, causal=True, block_q=512, block_k=512)
    return out


def test_flash_forward_compiles_for_v5e(one_chip):
    x = jax.ShapeDtypeStruct((8, 2048, 16, 128), jnp.bfloat16,
                             sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(_flash, x, x, x)


def test_flash_backward_compiles_for_v5e(one_chip):
    x = jax.ShapeDtypeStruct((8, 2048, 16, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return _flash(q, k, v).astype(jnp.float32).sum()

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
    assert text.count("tpu_custom_call") == 2  # forward, backward


@pytest.mark.parametrize("d,dtype,blocks,causal", [
    (64, jnp.bfloat16, (128, 128), True),     # ring attention's default
    (64, jnp.float32, (256, 512), True),      # float32 operands, one
    (128, jnp.float32, (256, 512), True),     # narrow step on the diagonal
    (64, jnp.bfloat16, (256, 512), False)])
def test_chunk_backward_compiles_with_traced_offsets(one_chip, d, dtype,
                                                     blocks, causal):
    """flash_attention_chunk's backward as ring attention calls it: traced
    offsets, a cotangent on lse, dq summed over several key tiles.  With
    float32 operands k's transpose reaches the dq matmul with no convert
    between (the compiler refused that before it went through scratch)."""
    x = jax.ShapeDtypeStruct((1, 2048, 8, d), dtype, sharding=one_chip)
    off = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def loss(q, k, v, q_off, kv_off):
        out, lse = attention.flash_attention_chunk(
            q, k, v, q_off, kv_off, causal=causal, block_q=blocks[0],
            block_k=blocks[1])
        return out.astype(jnp.float32).sum() + lse.sum()

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), x, x, x, off,
                          off)
    assert text.count("tpu_custom_call") == 2  # forward, backward


# The benchmark's cells (SmolLM2-1.7B: 32 heads of 64, sequence 2048):
# 5 rows on one chip, 40 rows under the fsdp=4 mesh.
CELL_ROWS = {"train-d12": 5, "train-fsdp4": 40}


def _roofline_kernel_pattern():
    """The pattern by which the benchmark's roofline reader finds the
    forward kernel in a trace (an HLO line's result and first operand)."""
    return face("dense_rope_swiglu", "flash_forward")


def _cell_calls(topo, monkeypatch, cell, fn, roped=False):
    """The kernel calls of fn(q, k, v) compiled at a cell's own attention
    shapes: on one chip or under the fsdp=4 mesh, through the public
    flash_attention.  roped: fn(q, k, v, cos, sin), the tables as `_block`
    gathers them, float32 [rows, 2048, 32]."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    on_tpu(monkeypatch)
    shape = (CELL_ROWS[cell], 2048, 32, 64)

    def args(sharding):
        x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)
        t = jax.ShapeDtypeStruct((shape[0], 2048, 32), jnp.float32,
                                 sharding=sharding)
        return (x, x, x) + ((t, t) if roped else ())

    if cell == "train-d12":
        return _custom_calls_as_traced(
            fn, *args(SingleDeviceSharding(topo.devices[0])))
    mesh = Mesh(topo.devices, ("fsdp",))
    with jax.sharding.set_mesh(mesh):
        return _custom_calls_as_traced(
            fn, *args(NamedSharding(mesh, P("fsdp"))))


@pytest.mark.parametrize("cell", sorted(CELL_ROWS))
def test_cell_flash_forward_keeps_the_face_the_roofline_reader_finds(
        topo, monkeypatch, cell):
    """One forward custom-call a layer, taking s32[2] offsets first and
    returning (out bf16[b, s, h x d], lse f32[bh, 8, s]): what
    benchmark/layer_metrics/flash_fwd_roofline.train.py matches.  A
    forward split in two, or a result of another rank or dtype, would
    turn that metric to null without failing anything else."""
    calls = _cell_calls(topo, monkeypatch, cell,
                        lambda q, k, v: attention.flash_attention(q, k, v))
    assert len(calls) == 1, calls
    found = re.search(_roofline_kernel_pattern(), calls[0])
    assert found, calls[0]
    rows = 5 if cell == "train-d12" else 10             # a chip's share
    assert f"(bf16[{rows},2048,2048]" in found.group(0)   # 32 heads x 64
    assert f"f32[{rows * 32},8,2048])" in found.group(0)


@pytest.mark.parametrize("cell", sorted(CELL_ROWS))
def test_cell_roped_flash_keeps_the_faces_and_takes_the_tables_last(
        topo, monkeypatch, cell):
    """The dense cells' call since PR 33, rope=(cos, sin): forward and ONE
    backward call compile for the v5e (one chip, and a chip's share under
    the fsdp=4 shard_map).  The forward's face, result and first operand,
    is the one the roofline reader finds; the backward's is three results
    behind s32[2], which no forward reader matches; the widened tables,
    float32 [rows, 2048, 128] for the two heads of 64 a program works, are
    the last two operands of each."""
    def loss(q, k, v, cos, sin):
        return attention.flash_attention(
            q, k, v, rope=(cos, sin)).astype(jnp.float32).sum()

    calls = _cell_calls(topo, monkeypatch, cell,
                        jax.grad(loss, argnums=(0, 1, 2)), roped=True)
    pattern = _roofline_kernel_pattern()
    forward = [l for l in calls if re.search(pattern, l)]
    backward = [l for l in calls if not re.search(pattern, l)]
    assert len(forward) == 1 and len(backward) == 1, calls
    rows = CELL_ROWS[cell] if cell == "train-d12" else CELL_ROWS[cell] // 4
    found = re.search(pattern, forward[0]).group(0)
    assert f"(bf16[{rows},2048,2048]" in found
    assert f"f32[{rows * 32},8,2048])" in found
    grad = f"bf16[{rows},2048,2048]"
    assert f"= ({grad}, {grad}, {grad}) custom-call(s32[2] " in backward[0]
    table = rf"f32\[{rows},2048,128\] [^,()]+"
    for line in calls:
        operands = line.split("custom-call(", 1)[1].split(")", 1)[0]
        assert re.search(rf", {table}, {table}$", operands), operands
        assert len(re.findall(r"f32\[\d+,2048,128\]", operands)) == 2
        # nothing a kernel takes or gives has a last axis under 128 lanes
        assert not re.search(r"\[[\d,]*,64\]", line), line


@pytest.mark.parametrize("cell", sorted(CELL_ROWS))
def test_cell_flash_backward_compiles_for_v5e(topo, monkeypatch, cell):
    def loss(q, k, v):
        return attention.flash_attention(q, k, v).astype(jnp.float32).sum()

    calls = _cell_calls(topo, monkeypatch, cell,
                        jax.grad(loss, argnums=(0, 1, 2)))
    assert len(calls) == 2, calls      # forward, backward
    # the backward kernel must NOT look like the forward to the reader
    pattern = _roofline_kernel_pattern()
    assert sum(bool(re.search(pattern, l)) for l in calls) == 1


@pytest.mark.parametrize("cell,window,grad", [
    ("train-d12", None, "bf16[5,2048,2048]"),
    ("train-fsdp4", None, "bf16[10,2048,2048]"),
    ("train-hybrid-d8", None, "bf16[1,8192,5120]"),
    ("train-hybrid-d8", 512, "bf16[1,8192,5120]")])
def test_cell_fused_backward_is_one_call_no_forward_reader_matches(
        topo, monkeypatch, cell, window, grad):
    """The backward at the three cells' shapes (a chip's share under
    fsdp=4; the hybrid's call causal and windowed, where dq is summed over
    4 and 16 key tiles) is ONE custom call with three results, dq, dk and
    dv, each in the operands' dtype.  Neither forward reader's pattern (a two-result (bf16, f32) tuple
    behind s32[2] or s32[3]) finds it, so flash_fwd_roofline.* and
    swa_fwd_roofline.hybrid keep reading the forward alone."""
    def attend(q, k, v):
        if cell != "train-hybrid-d8":
            return attention.flash_attention(q, k, v)
        return attention.flash_attention(q, k, v, sm_scale=0.125,
                                         window=window)

    def loss(q, k, v):
        return attend(q, k, v).astype(jnp.float32).sum()

    grads = jax.grad(loss, argnums=(0, 1, 2))
    if cell == "train-hybrid-d8":
        on_tpu(monkeypatch)
        x = jax.ShapeDtypeStruct(
            (1, 8192, 40, 128), jnp.bfloat16,
            sharding=SingleDeviceSharding(topo.devices[0]))
        calls = _custom_calls_as_traced(grads, x, x, x)
    else:
        calls = _cell_calls(topo, monkeypatch, cell, grads)
    forward_faces = (_roofline_kernel_pattern(),
                     face("sambay_hybrid", "swa_forward"))
    backward = [l for l in calls
                if not any(re.search(f, l) for f in forward_faces)]
    assert len(calls) == 2 and len(backward) == 1, calls
    offs = "s32[2]" if window is None else "s32[3]"
    assert f"= ({grad}, {grad}, {grad}) custom-call({offs} " in backward[0]


def test_traced_call_records_path_and_plan(one_chip, monkeypatch):
    on_tpu(monkeypatch)
    monkeypatch.setattr(attention.dispatch, "_taken", {})
    x = jax.ShapeDtypeStruct((5, 2048, 32, 64), jnp.bfloat16,
                             sharding=one_chip)
    jax.jit(lambda q, k, v: attention.flash_attention(q, k, v)).lower(x, x, x)
    taken = attention.dispatch.taken()
    assert taken["flash_attention"] == {"pallas": 1}
    (plan, times), = taken["flash_attention.plan"].items()
    sizes = ",".join(f"{name}{bq}x{bk}" for name, (bq, bk) in zip(
        ("fwd", "bwd"),
        attention.default_blocks(64, 2048, 2048, jnp.bfloat16)))
    assert plan.startswith(sizes + ",dq_in_pass,scale_folded,dead") \
        and times == 1
    assert plan.endswith(",operands_bshd,heads2x64")
    shares = plan.rsplit("dead", 1)[1].split("%")[0].split("/")
    assert len(shares) == 2 and all(0 < int(x) <= 20 for x in shares)
    # head size 128: the scale stays on the scores
    y = jax.ShapeDtypeStruct((8, 2048, 16, 128), jnp.bfloat16,
                             sharding=one_chip)
    jax.jit(lambda q, k, v: attention.flash_attention(q, k, v)).lower(y, y, y)
    assert any("scale_per_score" in p for p in
               attention.dispatch.taken()["flash_attention.plan"])


def test_flash_under_fsdp_mesh_is_shard_mapped(topo, monkeypatch):
    """GSPMD cannot partition a Mosaic kernel: under a four-chip mesh
    flash_attention must wrap it in a shard_map (batch over fsdp)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    on_tpu(monkeypatch)
    mesh = Mesh(topo.devices, ("fsdp",))
    x = jax.ShapeDtypeStruct((4, 2048, 14, 128), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("fsdp")))
    with jax.sharding.set_mesh(mesh):
        text = _compiled_text(
            lambda q, k, v: attention.flash_attention(q, k, v), x, x, x)
    assert "tpu_custom_call" in text

