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

# 1.1 B GQA serving widths (scripts/bench_decode.py, chip_smoke.py).
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
    assert text.count("tpu_custom_call") >= 2  # dq and dk/dv kernels


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
