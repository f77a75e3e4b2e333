"""AOT-compile ONE remat'd dense layer of the dense cells' widths for a
described v5e, on one chip (train-d12's 5 x 2048 rows) and under the fsdp=4
mesh (train-fsdp4's 40 rows): what the step does AROUND the flash kernels
(PR 33: the arrays XLA moves between the projections' matmul fusions and
the custom calls), what a layer's remat keeps (PR 40), the two programs'
digests and their scopes.

`_dense_layer_program` compiles once a module for each (mesh, policy) pair:
its ten cases share three compiles, which is why they share this file.
tests/aot.py says what such a compile is and is not, and holds what the
files of this name share.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from aot import (_compiled_text, _custom_calls_of, _hlo_bytes,
                 every_matmul_and_kernel_is_scoped, hlo_is_as_recorded, on_tpu)
from ray_tpu.ops import attention

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
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.models import transformer as tfm
    from ray_tpu.parallel.sharding import tree_shardings

    on_tpu(monkeypatch)
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
    _DENSE_LAYER[mesh_name, policy] = (compiled.as_text(),
                                       _custom_calls_of(compiled))
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
    on_tpu(monkeypatch)
    monkeypatch.setattr(attention.dispatch, "_taken", {})
    if kind == "latent":
        b, s, h, hidden = 2, 8192, 32, 2048

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
        b, s, h, d, hidden, window = ((1, 8192, 72, 128, 3072, 512)
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
        b, s, h, hidden = 1, 8192, 40, 2560

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


# sha256 of each program's optimised HLO, `aot._metadata_stripped`, as the
# tree BEFORE the scopes compiled it (PR 38's, 9d83a62); both have stood
# since (`aot.hlo_is_as_recorded` has the rule).
PARENT_HLO_SHA256 = {
    "one_chip":
        "f8ed670122d29fe6c37a4e2abeab135595d735282e71449a49a0e737920e6abf",
    "fsdp4":
        "18846b18d5b9aa4f38d225887a3735f29dea7670610c009b067599dd2824fbd8",
}


@pytest.mark.parametrize("mesh_name", sorted(PARENT_HLO_SHA256))
def test_the_scopes_left_the_optimised_hlo_as_the_parent_compiled_it(
        mesh_name, topo, monkeypatch):
    hlo_is_as_recorded(_dense_layer_program(topo, monkeypatch, mesh_name)[0],
                       PARENT_HLO_SHA256[mesh_name])


@pytest.mark.parametrize("mesh_name", sorted(PARENT_HLO_SHA256))
def test_every_matmul_and_every_kernel_carries_a_scope_of_the_vocabulary(
        mesh_name, topo, monkeypatch):
    every_matmul_and_kernel_is_scoped(
        _dense_layer_program(topo, monkeypatch, mesh_name)[0],
        whole_step=False)
