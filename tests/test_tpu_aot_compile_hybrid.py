"""AOT-compile the `train-hybrid-d8` cell for a described v5e
(Phi-4-mini-flash-reasoning's widths, eight layers of five kinds, 1 x 8192
tokens): the selective scan and the windowed flash call at the cell's
widths, the whole step program's bytes, its digest and its scopes.

tests/aot.py says what such a compile is and is not, and holds what the
files of this name share.
"""

import re

import jax
import jax.numpy as jnp

from aot import (_chip_bytes, _custom_calls_as_traced,
                 every_matmul_and_kernel_is_scoped, face, hlo_is_as_recorded,
                 on_tpu)
from ray_tpu.ops import attention

CONFIG = "phi4-mini-flash-train-d8.json"
HYBRID_ROWS, HYBRID_SEQ, D_INNER, D_STATE = 1, 8192, 5120, 16


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
    from ray_tpu.ops import selective_scan as ss

    on_tpu(monkeypatch, ss)
    monkeypatch.setattr(ss.dispatch, "_taken", {})
    forward, backward = face("sambay_hybrid", "scan_all")
    assert face("sambay_hybrid", "scan_forward") == forward
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
    on_tpu(monkeypatch)
    monkeypatch.setattr(attention.dispatch, "_taken", {})
    swa = face("sambay_hybrid", "swa_forward")
    full = face("sambay_hybrid", "flash_forward")
    # the dense cells' face
    assert full == face("dense_rope_swiglu", "flash_forward")
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
    compiled = step_program[0]
    total = _chip_bytes(compiled)
    assert 13.0 * 2 ** 30 < total < 15.75 * 2 ** 30, total / 2 ** 30
    text = compiled.as_text()
    # The two (mamba, window) pairs are ONE scanned body: a scan layer is
    # forward, forward again under remat, backward (3 calls), an attention
    # layer the same (3).  So the pair's body 6, the lone mamba 3, the full
    # layer 3, the cross layer 3.
    assert text.count("tpu_custom_call") == 6 + 3 + 3 + 3


# sha256 of the step program's optimised HLO, `aot._metadata_stripped`, as
# the tree BEFORE the scopes compiled it (PR 38's, 9d83a62); it has stood
# since (`aot.hlo_is_as_recorded` has the rule).
PARENT_HLO_SHA256 = (
    "3d480d458ec2cf6d978d269f5cdda6a3c7f2dcedd415d84a8be116758340a32b")


def test_the_scopes_left_the_optimised_hlo_as_the_parent_compiled_it(
        step_program):
    hlo_is_as_recorded(step_program[0].as_text(), PARENT_HLO_SHA256)


def test_every_matmul_and_every_kernel_carries_a_scope_of_the_vocabulary(
        step_program):
    every_matmul_and_kernel_is_scoped(step_program[0].as_text(),
                                      whole_step=True)
