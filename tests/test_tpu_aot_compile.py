"""AOT-compile the main path's Pallas kernels for a described v5e.

No chip is attached: the TPU compiler installed here compiles for a
topology that is described (v5e:2x2), at the widths of the 1.1 B GQA
model the repo serves and the 0.9 B model it trains.  What the chip's
compiler would refuse (tiling, VMEM, partitioning) it refuses here, at
no chip time.  A compile that passes is not a chip run.

The topology is described inside a fixture: only the xdist worker that
is handed this file loads libtpu.  Everything compiles in the test's
own process, with the persistent compile cache off (an entry written
for a described chip cannot be read back without one).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops import attention, paged_attention

# 1.1 B GQA serving widths (chip_smoke.py).
B, H, KVH, D = 128, 16, 4, 128
PAGE, NUM_PAGES = 128, 320


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
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


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


def _reader(name):
    """A layer-metric reader under benchmark/layer_metrics/, by file name."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("_reader", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    return reader


def _roofline_kernel_pattern():
    """The pattern by which the benchmark's roofline reader finds the
    forward kernel in a trace (an HLO line's result and first operand)."""
    return _reader("flash_fwd_roofline.train").KERNEL


def _cell_calls(topo, monkeypatch, cell, fn, roped=False):
    """The kernel calls of fn(q, k, v) compiled at a cell's own attention
    shapes: on one chip or under the fsdp=4 mesh, through the public
    flash_attention.  roped: fn(q, k, v, cos, sin), the tables as `_block`
    gathers them, float32 [rows, 2048, 32]."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    monkeypatch.setattr(attention.dispatch, "platform", lambda: "tpu")
    monkeypatch.setattr(attention.dispatch, "interpret_mode", lambda: False)
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
    import re

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
    import re

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
    import re

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
    import re

    def attend(q, k, v):
        if cell != "train-hybrid-d8":
            return attention.flash_attention(q, k, v)
        return attention.flash_attention(q, k, v, sm_scale=0.125,
                                         window=window)

    def loss(q, k, v):
        return attend(q, k, v).astype(jnp.float32).sum()

    grads = jax.grad(loss, argnums=(0, 1, 2))
    if cell == "train-hybrid-d8":
        _on_tpu(monkeypatch, attention)
        x = jax.ShapeDtypeStruct(
            (HYBRID_ROWS, HYBRID_SEQ, 40, 128), jnp.bfloat16,
            sharding=SingleDeviceSharding(topo.devices[0]))
        calls = _custom_calls_as_traced(grads, x, x, x)
    else:
        calls = _cell_calls(topo, monkeypatch, cell, grads)
    forward_faces = (_roofline_kernel_pattern(),
                     _reader("swa_fwd_roofline.hybrid").KERNEL)
    backward = [l for l in calls
                if not any(re.search(f, l) for f in forward_faces)]
    assert len(calls) == 2 and len(backward) == 1, calls
    offs = "s32[2]" if window is None else "s32[3]"
    assert f"= ({grad}, {grad}, {grad}) custom-call({offs} " in backward[0]


def test_traced_call_records_path_and_plan(one_chip, monkeypatch):
    monkeypatch.setattr(attention.dispatch, "platform", lambda: "tpu")
    monkeypatch.setattr(attention.dispatch, "interpret_mode", lambda: False)
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

    monkeypatch.setattr(attention.dispatch, "platform", lambda: "tpu")
    monkeypatch.setattr(attention.dispatch, "interpret_mode", lambda: False)
    mesh = Mesh(topo.devices, ("fsdp",))
    x = jax.ShapeDtypeStruct((4, 2048, 14, 128), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("fsdp")))
    with jax.sharding.set_mesh(mesh):
        text = _compiled_text(
            lambda q, k, v: attention.flash_attention(q, k, v), x, x, x)
    assert "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# The hybrid cell (train-hybrid-d8: Phi-4-mini-flash-reasoning's widths,
# 1 x 8192 tokens): selective scan, windowed flash, the step's bytes
# ---------------------------------------------------------------------------

HYBRID_ROWS, HYBRID_SEQ, D_INNER, D_STATE = 1, 8192, 5120, 16


def _on_tpu(monkeypatch, module):
    monkeypatch.setattr(module.dispatch, "platform", lambda: "tpu")
    monkeypatch.setattr(module.dispatch, "interpret_mode", lambda: False)


# cell -> its configuration under benchmark/configs/
STEP_CONFIGS = {"train-hybrid-d8": "phi4-mini-flash-train-d8.json",
                "train-moe-mla-d6": "kanana-2-30b-a3b-train-d6e16.json",
                "train-swa-moe-d5": "laguna-s-2.1-train-d5e8.json",
                "train-gdn-moe-d4": "qwen3-next-80b-a3b-train-d4e32.json"}


@pytest.fixture(scope="module")
def step_program(topo):
    """cell -> (the cell's whole step program as `ShardedTrainStep` jits
    it, compiled for one chip of the described v5e; what its trace left
    in `dispatch.taken()`; the configuration's train group).  Compiled
    when first asked for, once a module: a whole step takes a minute or
    two, and every test of a cell's step shares the one compile."""
    import copy
    import json

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark.drivers import train_model
    from ray_tpu.train.train_state import ShardedTrainStep, default_optimizer

    built = {}

    def build(cell):
        path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                            "benchmark", "configs", STEP_CONFIGS[cell])
        with open(path) as f:
            doc = json.load(f)
        tr = doc["train"]
        config = train_model.build_config(doc["program"], doc["model"], tr)
        mesh = Mesh(topo.devices[:1], ("fsdp",))
        whole = NamedSharding(mesh, P())
        ts = ShardedTrainStep(config, mesh, optimizer=default_optimizer(
            warmup_steps=tr["lr_warmup_steps"],
            total_steps=tr["lr_total_steps"],
            mu_dtype=jnp.bfloat16, nu_dtype=jnp.bfloat16))
        key = jax.eval_shape(lambda: jax.random.key(0))
        with pytest.MonkeyPatch.context() as mp:
            _on_tpu(mp, attention)      # the one dispatch module of all ops
            mp.setattr(attention.dispatch, "_taken", {})
            with jax.sharding.set_mesh(mesh):
                state = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                   sharding=whole),
                    jax.eval_shape(ts._init_fn, key))
                batch = {"tokens": jax.ShapeDtypeStruct(
                    (tr["batch_rows"], tr["sequence_length"] + 1), jnp.int32,
                    sharding=whole)}
                compiled = jax.jit(ts._step_fn, donate_argnums=(0,)).lower(
                    state, batch).compile()
            taken = copy.deepcopy(attention.dispatch.taken())
        return compiled, taken, tr

    def get(cell):
        if cell not in built:
            built[cell] = build(cell)
        return built[cell]

    return get


def _chip_bytes(compiled) -> int:
    from ray_tpu.util.device_stats import program_bytes

    return program_bytes(compiled.memory_analysis())


def _scan_shapes(one_chip):
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    b, t, c, n = HYBRID_ROWS, HYBRID_SEQ, D_INNER, D_STATE
    return (sds((b, t, c), jnp.bfloat16), sds((b, t, c)), sds((c, n)),
            sds((b, t, n)), sds((b, t, n)), sds((c,)))


def test_cell_scan_kernels_compile_and_keep_the_faces_the_readers_find(
        one_chip, monkeypatch):
    """Forward alone, forward with saved states and backward at the cell's
    widths; each custom-call is found by exactly the pattern that
    benchmark/scan_faces.py gives the scan readers for it."""
    import re

    from ray_tpu.ops import selective_scan as ss

    _on_tpu(monkeypatch, ss)
    monkeypatch.setattr(ss.dispatch, "_taken", {})
    forward, backward = _reader("selective_scan_share.hybrid").KERNELS
    assert _reader("selective_scan_roofline.hybrid").KERNEL == forward
    shapes = _scan_shapes(one_chip)
    calls = _custom_calls_as_traced(ss.selective_scan, *shapes)
    assert len(calls) == 1 and re.search(forward, calls[0]), calls
    assert not re.search(backward, calls[0])

    def loss(*a):
        return ss.selective_scan(*a).astype(jnp.float32).sum()

    calls = _custom_calls_as_traced(
        jax.grad(loss, argnums=tuple(range(6))), *shapes)
    assert len(calls) == 2, calls       # forward with states, backward
    assert sorted((bool(re.search(forward, l)), bool(re.search(backward, l)))
                  for l in calls) == [(False, True), (True, False)]
    taken = ss.dispatch.taken()
    assert taken["selective_scan"] == {"pallas": 2}
    assert list(taken["selective_scan.plan"]) == [
        "chunk128,channels1024,seq8192,state16"]


def test_cell_windowed_flash_compiles_and_is_told_from_the_full_call(
        one_chip, monkeypatch):
    """Differential attention's call at the cell's widths (40 heads, q and
    k padded to 128): windowed and full, forward and backward.  The
    windowed forward's first operand is s32[3], which is how
    swa_fwd_roofline.hybrid tells it from the full call's s32[2]
    (flash_fwd_roofline.hybrid)."""
    import re

    _on_tpu(monkeypatch, attention)
    monkeypatch.setattr(attention.dispatch, "_taken", {})
    swa = _reader("swa_fwd_roofline.hybrid").KERNEL
    full = _reader("flash_fwd_roofline.hybrid").KERNEL
    assert full == _roofline_kernel_pattern()   # the dense cells' face
    x = jax.ShapeDtypeStruct((HYBRID_ROWS, HYBRID_SEQ, 40, 128), jnp.bfloat16,
                             sharding=one_chip)
    for window, mine, other in ((512, swa, full), (None, full, swa)):
        def attend(q, k, v, window=window):
            return attention.flash_attention(q, k, v, sm_scale=0.125,
                                             window=window)

        def loss(q, k, v):
            return attend(q, k, v).astype(jnp.float32).sum()

        calls = _custom_calls_as_traced(attend, x, x, x)
        assert len(calls) == 1 and re.search(mine, calls[0]), calls
        assert not re.search(other, calls[0])
        assert "(bf16[1,8192,5120], f32[40,8,8192])" in calls[0]
        calls = _custom_calls_as_traced(jax.grad(loss, argnums=(0, 1, 2)),
                                        x, x, x)
        assert len(calls) == 2          # forward, backward
        assert sum(bool(re.search(mine, l)) for l in calls) == 1
        assert not any(re.search(other, l) for l in calls)
    plans = list(attention.dispatch.taken()["flash_attention.plan"])
    assert any(p.endswith(",window512,visited12.1%,operands_bshd,heads1x128")
               for p in plans), plans
    assert any("window" not in p for p in plans)


def test_cell_hybrid_step_program_fits_a_v5e(step_program):
    """The cell's whole step program (eight layers of five kinds, an
    eighth of the vocabulary, 1 x 8192 tokens, full remat, fused CE,
    bfloat16 moments) by AOT memory_analysis: under 15.75 GiB."""
    compiled, _, _ = step_program("train-hybrid-d8")
    total = _chip_bytes(compiled)
    assert 13.0 * 2 ** 30 < total < 15.75 * 2 ** 30, total / 2 ** 30
    text = compiled.as_text()
    # The two (mamba, window) pairs are ONE scanned body: a scan layer is
    # forward, forward again under remat, backward (3 calls), an attention
    # layer the same (3).  So the pair's body 6, the lone mamba 3, the full
    # layer 3, the cross layer 3.
    assert text.count("tpu_custom_call") == 6 + 3 + 3 + 3


# ---------------------------------------------------------------------------
# What the dense step does AROUND the flash kernels (PR 33): the arrays XLA
# moves between the projections' matmul fusions and the custom calls
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "pred": 1}


def _hlo_bytes(shapes: str) -> int:
    import re

    total = 0
    for dtype, dims in re.findall(r"\b(f32|bf16|s32|u32|pred)\[([0-9,]*)\]",
                                  shapes):
        n = 1
        for x in filter(None, dims.split(",")):
            n *= int(x)
        total += n * _DTYPE_BYTES[dtype]
    return total


# The ops that are a pass over their operands in the step's own stream.
# Not counted: bitcasts and tuple plumbing, which move nothing, and the
# asynchronous prefetches the scheduler wraps around a kernel's operands
# (slice-start / copy-start and their custom-call joins), which both the
# tree with rope in XLA and the one without have alike.
_PASSES = ("copy", "convert", "transpose", "fusion", "broadcast", "reduce",
           "pad", "concatenate", "slice", "dynamic-slice")


def _glue_between_matmuls_and_kernels(text: str):
    """{instruction name: (opcode, result shape, bytes read + written)} of
    every materialised op that lies between a Mosaic custom call and the
    nearest matmul fusions, walking from the calls' operands back and from
    their results on through anything that is neither (copies, converts,
    loop and reduce fusions, broadcasts), in every computation that holds
    a call (the scanned layer's forward body, and its remat + backward
    body).  Fused computations' insides are not materialised and are
    skipped."""
    import re

    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            inst = re.match(
                r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$", line)
            if inst:
                cur.append(inst.groups())
    with_dot = {name for name, insts in comps.items()
                if any(op in ("dot", "convolution") for _, _, op, _ in insts)}
    glue = {}
    for name, insts in comps.items():
        if "fused_computation" in name or name.startswith("fused_"):
            continue
        by_name = {i[0]: i for i in insts}

        def operands(i):
            return [o for o in re.findall(r"%([\w.\-]+)",
                                          i[3].split("), ")[0])
                    if o in by_name]

        def kernel(i):
            return i[2] == "custom-call" and "tpu_custom_call" in i[3]

        def matmul(i):
            called = re.search(r"calls=%?([\w.\-]+)", i[3])
            return i[2] in ("dot", "convolution") or (
                i[2] == "fusion" and called and called.group(1) in with_dot)

        users = {}
        for i in insts:
            for o in operands(i):
                users.setdefault(o, []).append(i[0])
        kernels = [i for i in insts if kernel(i)]
        seen = set()
        for start, step in (
                ([o for i in kernels for o in operands(i)],
                 lambda i: operands(i)),
                ([u for i in kernels for u in users.get(i[0], [])],
                 lambda i: users.get(i[0], []))):
            stack = list(start)
            while stack:
                i = by_name[stack.pop()]
                if i[0] in seen or kernel(i) or matmul(i) or i[2] in (
                        "parameter", "constant", "while", "tuple"):
                    continue
                seen.add(i[0])
                stack.extend(step(i))
        for n in seen:
            _, shape, op, _ = by_name[n]
            if op not in _PASSES:
                continue
            moved = _hlo_bytes(shape) + sum(
                _hlo_bytes(by_name[o][1]) for o in operands(by_name[n]))
            glue[f"{name}/{n}"] = (op, re.sub(r"\{[^}]*\}", "", shape), moved)
    return comps, glue


_DENSE_LAYER = {}       # (mesh, remat policy) -> (compiled text, its kernel
                        # calls as traced)


def _dense_layer_program(topo, monkeypatch, mesh_name, policy="full"):
    """One remat'd dense layer of the cells' widths, forward and backward
    (two scanned layers' grad: the scan body is compiled once), compiled
    once a module for `one_chip` (train-d12's 5 x 2048 rows) and for `fsdp4`
    (the fsdp=4 mesh, parameters sharded as ShardedTrainStep shards them,
    train-fsdp4's 40 rows): (the compiled text, the Mosaic custom-call
    lines as a trace names them)."""
    from jax._src.lib import _jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.models import transformer as tfm
    from ray_tpu.parallel.sharding import tree_shardings

    _on_tpu(monkeypatch, attention)
    monkeypatch.setattr(attention.dispatch, "_taken", {})
    if (mesh_name, policy) in _DENSE_LAYER:
        return _DENSE_LAYER[mesh_name, policy]
    config = tfm.TransformerConfig(
        vocab_size=256, hidden_size=2048, intermediate_size=8192,
        num_layers=2, num_heads=32, num_kv_heads=32, head_dim=64,
        max_seq_len=2048, rope_theta=130000.0, remat_policy=policy,
        dtype=jnp.bfloat16)
    shapes = jax.eval_shape(lambda: tfm.init_params(config, jax.random.key(0)))
    if mesh_name == "one_chip":
        mesh, rows = Mesh(topo.devices[:1], ("fsdp",)), 5
    else:
        mesh, rows = Mesh(topo.devices, ("fsdp",)), 40
    params = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        shapes, tree_shardings(mesh, tfm.logical_axes(config)))
    tokens = jax.ShapeDtypeStruct((rows, 2048), jnp.int32,
                                  sharding=NamedSharding(mesh, P("fsdp")))

    def loss(p, t):
        return tfm.forward_hidden(p, t, config)[0].astype(jnp.float32).sum()

    with jax.sharding.set_mesh(mesh):
        compiled = jax.jit(jax.grad(loss)).lower(params, tokens).compile()
    assert all(",rope_in_kernel,operands_bshd,heads2x64" in p for p in
               attention.dispatch.taken()["flash_attention.plan"])
    opts = _jax.HloPrintOptions.short_parsable()
    opts.print_operand_shape = True
    opts.include_layout_in_shapes = False
    opts.print_backend_config = False
    traced = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    _DENSE_LAYER[mesh_name, policy] = (compiled.as_text(), [
        l for l in traced.splitlines() if "tpu_custom_call" in l])
    return _DENSE_LAYER[mesh_name, policy]


def test_dense_layer_moves_q_and_k_to_the_kernels_once_and_unroped(
        topo, monkeypatch):
    """One remat'd dense layer of the cells' widths at train-d12's 5 x 2048
    rows, forward and backward.  With rope in XLA (PR 32) the float32 round
    trip of dq and dk, rope's split-and-pad fusions and its own passes made
    2.57 GB a layer move between the matmul fusions and the custom calls,
    as this walk counts them; with rope in the kernels (PR 33) 1.11 GB:
    twelve relayout copies of 42 MB, delta, the lse broadcasts and the
    tables; with q, k, v, do and out, dq, dk, dv crossing as [5, 2048, 32 x
    64] and delta made in the backward kernel (PR 38) 0.14 GB: the lse
    broadcasts and the tables.  A later change that puts ONE pass of a [5,
    2048, 32, 64] array back (84 MB) fails here, on the CPU."""
    import re

    text, _ = _dense_layer_program(topo, monkeypatch, "one_chip")
    assert text.count("tpu_custom_call") == 3   # forward, remat's, backward
    comps, glue = _glue_between_matmuls_and_kernels(text)
    # no float32 copy of a q- or k-sized array is materialised anywhere
    materialised = [
        (name, i[0], i[1]) for name, insts in comps.items()
        if "fused_computation" not in name for i in insts
        if re.match(r"f32\[(5,32,2048,64|5,2048,32,64|160,2048,64"
                    r"|5,2048,2048)\]", i[1])]
    assert not materialised, materialised
    # no split-and-concatenate of a 64-wide last axis (it compiles to a pad
    # and a maximum in one fusion)
    for name, insts in comps.items():
        ops = {i[2] for i in insts}
        padded = [i[1] for i in insts
                  if re.match(r"(bf16|f32)\[5,(2048,32|32,2048),", i[1])]
        assert not ({"pad", "maximum"} <= ops and padded), (name, padded)
    moved = sum(b for _, _, b in glue.values())
    assert 0.1e9 < moved < 0.2e9, (
        moved, sorted(glue.values(), key=lambda g: -g[2])[:20])


@pytest.mark.parametrize("mesh_name,rows", [("one_chip", 5), ("fsdp4", 10)])
def test_dense_layer_hands_the_kernels_what_the_projections_wrote(
        topo, monkeypatch, mesh_name, rows):
    """The same layer on one chip and as a chip's share under the fsdp=4
    mesh (10 rows, the parameters all-gathered): between a projection's
    matmul fusion and the flash custom calls, forward or backward, stands
    no copy or transpose of an operand-sized array (a q, k, v, do, out, dq,
    dk or dv: 42 MB at 5 rows), and nothing a kernel takes or gives has a
    last axis of 64 (half a lane block, which XLA pads and re-lays): q, k,
    v go as [rows, 2048, 2048] from the fusions that made them."""
    import re

    text, calls = _dense_layer_program(topo, monkeypatch, mesh_name)
    assert len(calls) == 3, calls
    operand = rows * 2048 * 2048 * 2
    _, glue = _glue_between_matmuls_and_kernels(text)
    relaid = [g for g in glue.values() if g[0] in ("copy", "transpose")
              and _hlo_bytes(g[1]) >= operand]
    assert not relaid, relaid
    whole = f"bf16[{rows},2048,2048]"
    for line in calls:
        assert not re.search(r"\[[\d,]*,64\]", line), line
        assert line.count(whole) >= 4, line     # out | dq dk dv and q, k, v
    assert not re.search(
        rf"bf16\[{rows},(32,2048|2048,32),64\]\S* (copy|transpose)\(", text)


# ---------------------------------------------------------------------------
# train-moe-mla-d6 (PR 34): latent attention's 192 / 128 flash calls, the
# grouped-matmul kernels and the cell's whole step program
# ---------------------------------------------------------------------------

MOE_ROWS, MOE_SEQ, MOE_HEADS = 2, 8192, 32
MOE_TOKENS, MOE_HELD, MOE_TOP_K = MOE_ROWS * MOE_SEQ, 16, 6


def _moe_faces():
    from benchmark import moe_faces

    return moe_faces


def test_cell_latent_flash_compiles_and_keeps_the_face_its_reader_finds(
        one_chip, monkeypatch):
    """Keys 192 wide, values 128, WHOLE operands at the cell's shapes (the
    cell itself has taken the parts since PR 35: the next test): ONE
    forward call that takes q, k as [2, 8192, 32 x 192] and v and gives out
    as [2, 8192, 32 x 128], two heads a program (384 and 256 lanes), one
    backward call with dq, dk and dv laid the same; nothing padded; the
    plan says both widths.  mla_fwd_roofline.moe's face, q bf16[bh, s, 192]
    second, is the parts' call's alone: this one no longer wears it."""
    import re

    _on_tpu(monkeypatch, attention)
    monkeypatch.setattr(attention.dispatch, "_taken", {})
    q = jax.ShapeDtypeStruct((MOE_ROWS, MOE_SEQ, MOE_HEADS, 192),
                             jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((MOE_ROWS, MOE_SEQ, MOE_HEADS, 128),
                             jnp.bfloat16, sharding=one_chip)
    face = _moe_faces().MLA_FORWARD
    assert _reader("mla_fwd_roofline.moe").KERNEL == face
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
    assert not re.search(face, forward[0]), forward
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
    assert len(calls) == 1 and not re.search(face, calls[0])
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
    import re

    _on_tpu(monkeypatch, attention)
    monkeypatch.setattr(attention.dispatch, "_taken", {})

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = (sds((MOE_ROWS, MOE_SEQ, MOE_HEADS, 192)),
              sds((MOE_ROWS, MOE_SEQ, MOE_HEADS, 256)),
              sds((MOE_ROWS, MOE_SEQ, 64)),
              sds((MOE_ROWS, MOE_SEQ, 32), jnp.float32),
              sds((MOE_ROWS, MOE_SEQ, 32), jnp.float32))
    face = _moe_faces().MLA_FORWARD

    def attend(q, kv, k_pe, cos, sin):
        return attention.latent_flash_attention(q, kv, k_pe, (cos, sin),
                                                sm_scale=192 ** -0.5)

    calls = _custom_calls_as_traced(attend, *shapes)
    assert len(calls) == 1 and re.search(face, calls[0]), calls
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
    backward = [l for l in calls if not re.search(face, l)]
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
    import re

    from ray_tpu.ops import grouped_matmul as gm

    _on_tpu(monkeypatch, gm)
    monkeypatch.setattr(gm.dispatch, "_taken", {})
    faces = _moe_faces()
    patterns = {"forward": faces.GROUPED_FORWARD,
                "transposed": faces.GROUPED_TRANSPOSED,
                "dw": faces.GROUPED_DW}
    assert _reader("grouped_matmul_roofline.moe").KERNEL == patterns["forward"]
    assert _reader("grouped_matmul_share.moe").KERNELS == tuple(
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


def _every_face():
    """name -> pattern: every face a reader of the expert cells looks for."""
    from benchmark import gdn_faces, moe_faces, swa_moe_faces

    return {f"{m.__name__}.{n}": p for m in (moe_faces, gdn_faces,
                                             swa_moe_faces)
            for n, p in vars(m).items()
            if n.isupper() and n[0] != "_" and isinstance(p, str)}


def _grouped_calls(calls):
    """How many of a program's custom calls the grouped readers find."""
    import re

    faces = _moe_faces()
    return sum(bool(re.search(p, l)) for l in calls
               for p in (faces.GROUPED_FORWARD, faces.GROUPED_TRANSPOSED,
                         faces.GROUPED_DW))


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
    import re

    from ray_tpu.ops import row_gather as rg

    _on_tpu(monkeypatch, rg)
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
    compiled, taken, tr = step_program("train-moe-mla-d6")
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


# ---------------------------------------------------------------------------
# The windowed / full GQA, routed-expert cell (train-swa-moe-d5): a window
# WITH rope at head size 128, 72 and 48 heads, 8 experts of 3072 <-> 1024
# ---------------------------------------------------------------------------

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
    import re

    from benchmark import swa_moe_faces as faces

    _on_tpu(monkeypatch, attention)
    monkeypatch.setattr(attention.dispatch, "_taken", {})
    swa = _reader("swa_fwd_roofline.swamoe").KERNEL
    full = _reader("flash_fwd_roofline.swamoe").KERNEL
    assert (swa, full) == (faces.FORWARD_WINDOWED, faces.FORWARD_FULL)
    assert _reader("attention_share.swamoe").KERNELS == (
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
    import re

    from ray_tpu.ops import grouped_matmul as gm

    _on_tpu(monkeypatch, gm)
    faces = _moe_faces()
    patterns = {"forward": faces.GROUPED_FORWARD,
                "transposed": faces.GROUPED_TRANSPOSED,
                "dw": faces.GROUPED_DW}
    assert _reader("grouped_matmul_roofline.swamoe").KERNEL \
        == patterns["forward"]
    assert _reader("grouped_matmul_share.swamoe").KERNELS == tuple(
        patterns.values())

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
    compiled, taken, tr = step_program("train-swa-moe-d5")
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


# ---------------------------------------------------------------------------
# train-gdn-moe-d4 (PR 42): the gated-delta-rule kernels and the head-256
# flash call at the cell's size, and its step program
# ---------------------------------------------------------------------------
GDN_ROWS, GDN_SEQ = 3, 8192


def test_cell_gated_delta_kernels_compile_and_keep_the_faces_readers_find(
        one_chip, monkeypatch):
    """Forward alone, forward with the blocks' first states and backward at
    the cell's size (3 x 8192, 32 value heads over 16 key heads of 128,
    bfloat16 operands); each custom-call is found by exactly the pattern
    benchmark/gdn_faces.py gives the rule's readers for it, and by none of
    the flash or grouped-matmul patterns the cell's other readers use."""
    import re

    from benchmark import gdn_faces, moe_faces
    from ray_tpu.ops import gated_delta as gd

    _on_tpu(monkeypatch, gd)
    monkeypatch.setattr(gd.dispatch, "_taken", {})
    forward, backward = _reader("gated_delta_share.gdn").KERNELS
    assert (forward, backward) == (gdn_faces.RULE_FORWARD,
                                   gdn_faces.RULE_BACKWARD)
    assert _reader("gated_delta_fwd_roofline.gdn").KERNEL == forward
    others = (gdn_faces.FLASH_FORWARD, moe_faces.GROUPED_FORWARD,
              moe_faces.GROUPED_TRANSPOSED, moe_faces.GROUPED_DW)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    b, t = GDN_ROWS, GDN_SEQ
    shapes = (sds((b, t, 16, 128)), sds((b, t, 16, 128)),
              sds((b, t, 32, 128)), sds((b, t, 32), jnp.float32),
              sds((b, t, 32), jnp.float32))
    calls = _custom_calls_as_traced(gd.gated_delta_rule, *shapes)
    assert len(calls) == 1 and re.search(forward, calls[0]), calls
    assert not re.search(backward, calls[0])
    assert "= bf16[3,8192,4096] custom-call(bf16[3,8192,2048] " in calls[0]

    def loss(*a):
        return gd.gated_delta_rule(*a).astype(jnp.float32).sum()

    calls = _custom_calls_as_traced(
        jax.grad(loss, argnums=tuple(range(5))), *shapes)
    assert len(calls) == 2, calls       # forward with states, backward
    assert sorted((bool(re.search(forward, l)), bool(re.search(backward, l)))
                  for l in calls) == [(False, True), (True, False)]
    # every block of 8 chunks' first state, a head: [3 x 32, 16, 128, 128]
    assert any("f32[96,16,128,128]" in l for l in calls)
    assert not any(re.search(o, l) for o in others for l in calls)
    taken = gd.dispatch.taken()
    assert taken["gated_delta_rule"] == {"pallas": 2}
    assert list(taken["gated_delta_rule.plan"]) == [
        "chunk64,heads32over16,dk128,dv128,state_f32,bwd_pallas,"
        "passes28.5+50.5"]


def test_cell_head_256_flash_compiles_and_keeps_the_face_its_reader_finds(
        one_chip, monkeypatch):
    """The full layer's call at 3 x 8192, 16 heads of 256, the quarter rope
    as tables with an identity tail: forward and backward compile (they ask
    70 and 96 MiB of VMEM at this width and length, the tables in ONE
    buffer each and the backward's key tile 1024); the forward is found by
    flash_fwd_roofline.gdn, the backward is not; the plan says one head a
    program."""
    import re

    from benchmark import gdn_faces

    _on_tpu(monkeypatch, attention)
    monkeypatch.setattr(attention.dispatch, "_taken", {})
    face = _reader("flash_fwd_roofline.gdn").KERNEL
    assert face == gdn_faces.FLASH_FORWARD
    x = jax.ShapeDtypeStruct((GDN_ROWS, GDN_SEQ, 16, 256), jnp.bfloat16,
                             sharding=one_chip)
    table = jax.ShapeDtypeStruct((GDN_ROWS, GDN_SEQ, 128), jnp.float32,
                                 sharding=one_chip)

    def attend(q, k, v, cos, sin):
        return attention.flash_attention(q, k, v, sm_scale=1.0 / 16,
                                         rope=(cos, sin))

    def loss(*a):
        return attend(*a).astype(jnp.float32).sum()

    calls = _custom_calls_as_traced(jax.grad(loss, argnums=(0, 1, 2)),
                                    x, x, x, table, table)
    assert len(calls) == 2          # forward, backward
    assert sum(bool(re.search(face, l)) for l in calls) == 1
    assert any("(bf16[3,8192,4096], f32[48,8,8192])" in l for l in calls)
    assert not any(re.search(gdn_faces.RULE_FORWARD, l)
                   or re.search(gdn_faces.RULE_BACKWARD, l) for l in calls)
    assert list(attention.dispatch.taken()["flash_attention.plan"]) == [
        "fwd2048x512,bwd512x1024,dq_in_pass,dq_over8tiles,scale_folded,"
        "dead6/6%,rope_in_kernel,operands_bshd,heads1x256"]


def test_cell_mixer_chain_kernels_compile_and_wear_no_readers_face(
        one_chip, monkeypatch):
    """ops/mixer_chain.py at the cell's size (3 x 8192, 16 key and 32 value
    heads of 128, four taps): the forward and the backward each compile for
    a v5e as ONE custom call.  Both begin with a bf16 3-D operand, as the
    rule's kernels do; the rule's patterns read on to the fifth operand
    (forward) and the five results (backward), which keeps them apart: none
    of the readers' patterns finds either."""
    import re

    from ray_tpu.ops import mixer_chain as mc

    _on_tpu(monkeypatch, mc)
    monkeypatch.setattr(mc.dispatch, "_taken", {})
    b, t = GDN_ROWS, GDN_SEQ
    qkv = jax.ShapeDtypeStruct((b, t, 8192), jnp.bfloat16, sharding=one_chip)
    conv_w = jax.ShapeDtypeStruct((4, 8192), jnp.float32, sharding=one_chip)

    def chain(qkv, conv_w):
        return mc.conv_silu_l2norm(qkv, conv_w, 16, 128, 128 ** -0.5)

    def loss(qkv, conv_w):
        q, k, v = chain(qkv, conv_w)
        return sum(jnp.sum(jnp.square(a.astype(jnp.float32)))
                   for a in (q, k, v))

    faces = _every_face()
    assert len(faces) == 4 + 3 + 3
    forward = _custom_calls_as_traced(chain, qkv, conv_w)
    assert len(forward) == 1
    assert ("= (bf16[3,8192,2048], bf16[3,8192,2048], bf16[3,8192,4096]) "
            "custom-call(bf16[3,8192,8192] ") in forward[0]
    both = _custom_calls_as_traced(jax.grad(loss, argnums=(0, 1)), qkv,
                                   conv_w)
    assert len(both) == 2
    backward = [l for l in both
                if "= (bf16[3,8192,8192], f32[32,8192]) custom-call("
                "bf16[3,8192,8192] " in l]
    assert len(backward) == 1
    for line in forward + both:
        assert not [n for n, p in faces.items() if re.search(p, line)], line
    assert mc.dispatch.taken()["mixer_chain"] == {"pallas": 2}
    assert mc._plan(qkv, 16, 128) == (512, 256, 8, 16, 4096)
    assert mc._plan(qkv, 16, 128, mc.BACKWARD_HEADS)[:2] == (512, 128)


def test_cell_linear_mixer_gradient_moves_the_chain_once(one_chip,
                                                         monkeypatch):
    """The compiled gradient of ONE linear mixer at the cell's size: five
    kernels (the chain's forward, the rule's forward with its states, and
    for the backward the chain's forward AGAIN, from the layer's input,
    the rule's backward and the chain's; the rule's forward is not run
    again: its o and states are kept); between W_qkvz's product and them
    no copy of v out of qkv (PR 45's program held `slice` bf16[3, 8192,
    4096]) and, behind the rule's backward, no [b, t, key heads, group,
    d_k] view of dq and dk, which the compiler tiled T(2,128) and re-laid
    twice."""
    import json
    import re

    from benchmark.drivers import train_model
    from ray_tpu.models import gdn_moe as gm

    _on_tpu(monkeypatch, attention)
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "configs",
                        STEP_CONFIGS["train-gdn-moe-d4"])
    with open(path) as f:
        doc = json.load(f)
    config = train_model.build_config(doc["program"], doc["model"],
                                      doc["train"])
    assert config.conv_channels == 8192
    lp = {name: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
          for name, (shape, _, _) in gm._layer_shapes(gm.LINEAR,
                                                      config).items()}
    x = jax.ShapeDtypeStruct((GDN_ROWS, GDN_SEQ, config.hidden_size),
                             jnp.bfloat16, sharding=one_chip)
    assert config.remat

    def loss(x, lp):
        return jnp.sum(gm._linear_mixer(x, lp, config).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        x, lp).compile().as_text()
    assert text.count("tpu_custom_call") == 5
    assert text.count("gated_delta_fwd") and len(
        [l for l in text.splitlines() if "tpu_custom_call" in l
         and "gated_delta_fwd" in l]) == 1
    lines = text.splitlines()
    assert not [l for l in lines if "[3,8192,16,2,128]" in l]
    assert not [l for l in lines if "T(2,128)" in l and " reshape(" in l
                and "[3,8192," in l]
    assert not [l for l in lines
                if re.search(r"= bf16\[3,8192,4096\]\S* slice\(", l)]
    chain = [l for l in lines if "tpu_custom_call" in l
             and "ssm.chain" in l]
    assert len(chain) == 3, chain


def test_cell_gdn_moe_step_program_fits_a_v5e(step_program):
    """The cell's whole step program (three gated-delta-rule layers and one
    gated full layer, 32 of 512 experts and a gated shared expert in each,
    an eighth of the vocabulary, 3 x 8192 tokens, full remat, fused CE,
    bfloat16 moments) by AOT memory_analysis: under 15.75 GiB at the
    configuration's rows."""
    compiled, taken, tr = step_program("train-gdn-moe-d4")
    assert tr["batch_rows"] == GDN_ROWS and tr["sequence_length"] == GDN_SEQ
    total = _chip_bytes(compiled)
    assert 13.0 * 2 ** 30 < total < 15.75 * 2 ** 30, total / 2 ** 30
    # The linear segment: the rule's forward, its forward again under remat
    # and its backward (3), and the chain in front of it likewise and once
    # more for the rule's backward (4, PR 46: `gdn_moe._linear_mixer`); the
    # full segment the flash three; each segment the grouped kernels,
    # twelve at each of the layer's two buffer sizes, and the two movers by
    # the token beside them (PR 45).
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3 + 4 + 3 + 2 * 2 * (12 + 2)
    chain = [l for l in text.splitlines()
             if "tpu_custom_call" in l and "ssm.chain" in l]
    assert len(chain) == 4 and all("/ssm/" in l for l in chain), chain
    assert "[3,8192,16,2,128]" not in text      # the group's view is gone
    assert set(taken["mixer_chain"]) == {"pallas"}
    assert _grouped_calls(_custom_calls_of(compiled)) == 2 * 2 * 12
    assert set(taken["routed_experts"]) == {"pallas"}
    assert sorted(taken["routed_experts.plan"]) == [
        "rows_by_index,slots245760,buffer253952,entries<=245760",
        "rows_by_index,slots245760,buffer69632,entries<=61440"]
    assert list(taken["gated_delta_rule.plan"]) == [
        "chunk64,heads32over16,dk128,dv128,state_f32,bwd_pallas,"
        "passes28.5+50.5"]
    assert [p.split(",dead")[1] for p in taken["flash_attention.plan"]] == [
        "6/6%,rope_in_kernel,operands_bshd,heads1x256"]
    assert list(taken["gdn_moe.rope"]) == [
        "full_attention:in_kernel64of256_columns_reordered_at_use_identity_"
        "tail"]
    assert all(",groups32" in p for p in taken["grouped_matmul.plan"])


# ---------------------------------------------------------------------------
# What a layer's remat keeps (PR 40): under the ladder's first rung
# (`ShardedTrainStep`, "save_attn" in `models/common.maybe_remat`) the
# compiled gradient of one remat'd layer holds ONE forward flash call and
# one backward; under the second ("full": bare jax.checkpoint) two and one
# ---------------------------------------------------------------------------


def _flash_calls(text: str):
    """(forward, backward) flash custom calls of a compiled module, told
    apart by the kernels' names in `op_name`."""
    calls = [l for l in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    return (sum("/flash_fwd/" in l for l in calls),
            sum("/flash_bwd/" in l for l in calls))


@pytest.mark.parametrize("mesh_name,policy,forwards", [
    ("fsdp4", "save_attn", 1), ("one_chip", "full", 2), ("fsdp4", "full", 2)])
def test_dense_layer_runs_the_flash_forward_once_where_out_and_lse_are_kept(
        topo, monkeypatch, time_limit, mesh_name, policy, forwards):
    """The scanned dense layer of the cells' widths under the fsdp=4 mesh,
    where the custom VJP sits INSIDE the `shard_map` and the kept out and
    lse cross it (the policy sees the names in there), and the bare layer
    on both meshes (programs other tests compiled).
    What is kept is the kernel's own [rows, 2048, 32 x 64] and [rows x 32,
    2048] float32, a layer: not the [rows, 2048, 32, 64] view, which would
    lie in half-filled lane blocks at twice the bytes."""
    import re

    time_limit(240)
    text, calls = _dense_layer_program(topo, monkeypatch, mesh_name, policy)
    assert _flash_calls(text) == (forwards, 1), calls
    rows = 5 if mesh_name == "one_chip" else 10
    stacked = set(re.findall(r"(?:bf16|f32)\[2,[\d,]+\]", text))
    assert f"bf16[2,{rows},2048,2048]" in stacked       # the layers' inputs
    assert (f"f32[2,{rows * 32},2048]" in stacked) == (policy == "save_attn")
    assert f"bf16[2,{rows},2048,32,64]" not in stacked


def _remat_layer_calls(layer, policy, *shapes):
    """(forward, backward) flash calls in the compiled value and gradient,
    by every operand, of `layer` under `maybe_remat(.., policy)` (the value
    too, as a step wants the loss: the layer's first forward is not dead)."""
    from ray_tpu.models import common

    block = common.maybe_remat(layer, True, policy)

    def loss(*operands):
        return block(*operands).astype(jnp.float32).sum()

    return _flash_calls(_compiled_text(
        jax.value_and_grad(loss, argnums=tuple(range(len(shapes)))),
        *shapes))


def _sds(one_chip, *shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("kind,policy,forwards", [
    ("dense_rope_64", "save_attn", 1), ("latent", "save_attn", 1),
    ("windowed_rope_128", "save_attn", 1), ("cross", "save_attn", 1),
    ("cross", "full", 2)])
def test_a_remat_layer_of_every_attention_entry_keeps_out_and_lse(
        one_chip, monkeypatch, time_limit, kind, policy, forwards):
    """Projections, the attention entry and W_o as one remat'd layer, at
    the cells' widths: latent attention's parts (train-moe-mla-d6: 32
    heads, keys 192, values 128), the dense cells' roped call on one chip
    (train-d12: 5 x 2048, 32 heads of 64), a window WITH rope at head 128
    (train-swa-moe-d5's sliding layer, 72 heads) and the hybrid's cross
    layer, whose keys and values come from another layer (operands of the
    layer, 40 heads at value width 128): the backward of the kept layer
    holds no forward kernel, that of the bare one holds it again."""
    time_limit(240)
    _on_tpu(monkeypatch, attention)
    monkeypatch.setattr(attention.dispatch, "_taken", {})
    if kind == "latent":
        b, s, h, hidden = MOE_ROWS, MOE_SEQ, MOE_HEADS, 2048

        def layer(x, wq, wkv, wr, wo, cos, sin):
            q = (x @ wq).reshape(b, s, h, 192)
            kv = (x @ wkv).reshape(b, s, h, 256)
            out = attention.latent_flash_attention(q, kv, x @ wr, (cos, sin))
            return out.reshape(b, s, h * 128) @ wo

        shapes = [_sds(one_chip, b, s, hidden),
                  _sds(one_chip, hidden, h * 192),
                  _sds(one_chip, hidden, h * 256), _sds(one_chip, hidden, 64),
                  _sds(one_chip, h * 128, hidden)] + [
            _sds(one_chip, b, s, 32, dtype=jnp.float32)] * 2
    elif kind in ("windowed_rope_128", "dense_rope_64"):
        b, s, h, d, hidden, window = ((1, SWA_SEQ, 72, 128, 3072, 512)
                                      if kind == "windowed_rope_128"
                                      else (5, 2048, 32, 64, 2048, None))

        def layer(x, wq, wk, wv, wo, cos, sin):
            q, k, v = ((x @ w).reshape(b, s, h, d) for w in (wq, wk, wv))
            out = attention.flash_attention(q, k, v, window=window,
                                            rope=(cos, sin))
            return out.reshape(b, s, h * d) @ wo

        shapes = [_sds(one_chip, b, s, hidden)] + [
            _sds(one_chip, hidden, h * d)] * 3 + [
            _sds(one_chip, h * d, hidden)] + [
            _sds(one_chip, b, s, d // 2, dtype=jnp.float32)] * 2
    else:
        b, s, h, hidden = HYBRID_ROWS, HYBRID_SEQ, 40, 2560

        def layer(x, k, v, wq, wo):
            q = (x @ wq).reshape(b, s, h, 128)
            out = attention.flash_attention(q, k, v, sm_scale=0.125)
            return out.reshape(b, s, h * 128) @ wo

        shapes = [_sds(one_chip, b, s, hidden)] + [
            _sds(one_chip, b, s, h, 128)] * 2 + [
            _sds(one_chip, hidden, h * 128), _sds(one_chip, h * 128, hidden)]
    assert _remat_layer_calls(layer, policy, *shapes) == (forwards, 1)
    assert attention.dispatch.taken()["flash_attention"] == {"pallas": 1}
    (plan,) = attention.dispatch.taken()["flash_attention.plan"]
    assert {"latent": "latent_parts", "windowed_rope_128": "window512",
            "dense_rope_64": "rope_in_kernel,operands_bshd,heads2x64",
            "cross": "dead"}[kind] in plan, plan


# ---------------------------------------------------------------------------
# The scope vocabulary (PR 39, models/common.py): the model's parts named
# in the compiled program's metadata, and in nothing else of it
# ---------------------------------------------------------------------------

# sha256 of each program's optimised HLO, `_metadata_stripped`, as the
# tree BEFORE the scopes compiled it (PR 38's, 9d83a62: this file's
# helpers run on an archive of that commit).  A scope is metadata: it may move no fusion, no schedule
# and no byte of a kernel.  A change that means to move the program
# replaces its digest here and says so.  PR 45 MEANT TO: the two expert
# cells' digests are its tree's (the movers by the token are a kernel,
# ops/row_gather.py); the hybrid's and both dense layers' stand.
# `train-gdn-moe-d4` is PR 46's tree (40fa1e4), written down before PR 47
# moved the model files' shared stack into models/stack.py, as is
# `train-cca-moe-d4` in tests/test_tpu_aot_compile_cca.py.  PR 50 MEANT TO:
# the three expert cells' digests here (and the two in the `_cca` and `_ssd`
# files) are its tree's: ops/grouped_matmul.py's forward / transposed grid
# walks a column block's row tiles before the next column block, and
# `train-swa-moe-d5`'s matrices are one block where they were two; in
# `train-moe-mla-d6` and `train-gdn-moe-d4` (one block before and after) the
# kernel's two grid axes changed places and nothing else.  PR 49's tree read
# a601fb23.., 9846286b.., 7cc70467..; the hybrid's and both dense layers'
# stand.
PARENT_HLO_SHA256 = {
    "train-hybrid-d8":
        "3d480d458ec2cf6d978d269f5cdda6a3c7f2dcedd415d84a8be116758340a32b",
    "train-moe-mla-d6":
        "3ca4b7c9c283cef8b41c7806c1711cd6c60458da9f93049b76045b9bd299f8dd",
    "train-swa-moe-d5":
        "f2e033300b79a9054556a89eb396fc3ad498458c8b1ff89950c36042f0fb56f1",
    "train-gdn-moe-d4":
        "2ad23ab37fda3cc27b1a3cd8dfebd2e407048da425c9639c2c89d28d2f2004a3",
    "dense-layer.one_chip":
        "f8ed670122d29fe6c37a4e2abeab135595d735282e71449a49a0e737920e6abf",
    "dense-layer.fsdp4":
        "18846b18d5b9aa4f38d225887a3735f29dea7670610c009b067599dd2824fbd8",
}


def _kernel_without_locations(body: str) -> str:
    """sha256 of a Mosaic kernel (a custom call's `body`: base64 of MLIR
    bytecode) printed without its debug locations, which hold the CALL
    SITE's file, function and line in the model files."""
    import base64
    import hashlib

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
    import re

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


def _program_text(program, step_program, topo, monkeypatch) -> str:
    if program in STEP_CONFIGS:
        return step_program(program)[0].as_text()
    return _dense_layer_program(topo, monkeypatch,
                                program.split(".", 1)[1])[0]


@pytest.mark.parametrize("program", sorted(PARENT_HLO_SHA256))
def test_the_scopes_left_the_optimised_hlo_as_the_parent_compiled_it(
        program, step_program, topo, monkeypatch):
    import hashlib

    text = _metadata_stripped(
        _program_text(program, step_program, topo, monkeypatch))
    assert "op_name" not in text and "source_file" not in text \
        and ".py" not in text
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PARENT_HLO_SHA256[program]


@pytest.mark.parametrize("program", sorted(PARENT_HLO_SHA256))
def test_every_matmul_and_every_kernel_carries_a_scope_of_the_vocabulary(
        program, step_program, topo, monkeypatch):
    """What the `part_ms.*` readers rest on: in the compiled program every
    instruction a trace can show that is a Pallas kernel or holds a matmul
    (a fusion's root gives it its `op_name`) names one of
    `models/common.py`'s scopes, forward, remat's second forward and
    backward alike."""
    import re

    from ray_tpu.models import common
    from ray_tpu.util.device_stats import hlo_instructions

    scope = re.compile(r"(?<![\w.])(" + "|".join(
        re.escape(s) for s in common.SCOPES) + r")(?![\w.])")
    module, rows = hlo_instructions(
        _program_text(program, step_program, topo, monkeypatch))
    assert module.startswith("jit_")
    heavy = {name: row for name, row in rows.items()
             if row[2] or row[3] == "tpu_custom_call"}
    assert len(heavy) >= 10, sorted(heavy)
    bare = {name: row[1] for name, row in heavy.items()
            if not scope.search(row[1])}
    assert not bare, bare
    if program in STEP_CONFIGS:     # the whole step: both ends and the rest
        found = {s for row in rows.values() for s in scope.findall(row[1])}
        assert {common.EMBED, common.LOSS, common.OPTIMIZER,
                common.MLP} <= found, found
