"""AOT-compile the `train-gdn-moe-d4` cell for a described v5e (PR 42: three
gated-delta-rule layers to one gated full layer at head 256, 32 of 512
experts and a gated shared expert, 3 x 8192 tokens): the rule's kernels, the
head-256 flash call and the mixer chain at the cell's size, one linear
mixer's gradient, the whole step program's bytes and plans, its digest and
its scopes.

tests/aot.py says what such a compile is and is not, and holds what the
files of this name share.
"""

import re

import jax
import jax.numpy as jnp

from aot import (_chip_bytes, _custom_calls_as_traced, _custom_calls_of,
                 _every_face, _grouped_calls, config_doc,
                 every_matmul_and_kernel_is_scoped, face, hlo_is_as_recorded,
                 on_tpu)
from ray_tpu.ops import attention

CONFIG = "qwen3-next-80b-a3b-train-d4e32.json"
GDN_ROWS, GDN_SEQ = 3, 8192


def test_cell_gated_delta_kernels_compile_and_keep_the_faces_readers_find(
        one_chip, monkeypatch):
    """Forward alone, forward with the blocks' first states and backward at
    the cell's size (3 x 8192, 32 value heads over 16 key heads of 128,
    bfloat16 operands); each custom-call is found by exactly the pattern
    benchmark/gdn_faces.py gives the rule's readers for it, and by none of
    the flash or grouped-matmul patterns the cell's other readers use."""
    from benchmark import gdn_faces, moe_faces
    from ray_tpu.ops import gated_delta as gd

    on_tpu(monkeypatch, gd)
    monkeypatch.setattr(gd.dispatch, "_taken", {})
    forward, backward = face("qwen3_next_gdn_moe", "rule_all")
    assert (forward, backward) == (gdn_faces.RULE_FORWARD,
                                   gdn_faces.RULE_BACKWARD)
    assert face("qwen3_next_gdn_moe", "rule_forward") == forward
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
    from benchmark import gdn_faces

    on_tpu(monkeypatch)
    monkeypatch.setattr(attention.dispatch, "_taken", {})
    flash = face("qwen3_next_gdn_moe", "flash_forward")
    assert flash == gdn_faces.FLASH_FORWARD
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
    assert sum(bool(re.search(flash, l)) for l in calls) == 1
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
    from ray_tpu.ops import mixer_chain as mc

    on_tpu(monkeypatch, mc)
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
    from benchmark.drivers import train_model
    from ray_tpu.models import gdn_moe as gm

    on_tpu(monkeypatch)
    doc = config_doc(CONFIG)
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
    compiled, taken, tr, _ = step_program
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


# sha256 of the step program's optimised HLO, `aot._metadata_stripped`
# (`aot.hlo_is_as_recorded` has the rule).  PR 50 MEANT TO move it, and this
# is its tree's: ops/grouped_matmul.py's forward / transposed grid walks a
# column block's row tiles before the next column block; this cell's
# matrices were one block before and after, the kernel's two grid axes
# changed places and nothing else (PR 49's tree read 7cc70467..; PR 46's,
# 40fa1e4, stood before PR 47 moved the model files' shared stack into
# models/stack.py).
PARENT_HLO_SHA256 = (
    "2ad23ab37fda3cc27b1a3cd8dfebd2e407048da425c9639c2c89d28d2f2004a3")


def test_the_scopes_left_the_optimised_hlo_as_the_parent_compiled_it(
        step_program):
    hlo_is_as_recorded(step_program[0].as_text(), PARENT_HLO_SHA256)


def test_every_matmul_and_every_kernel_carries_a_scope_of_the_vocabulary(
        step_program):
    every_matmul_and_kernel_is_scoped(step_program[0].as_text(),
                                      whole_step=True)
