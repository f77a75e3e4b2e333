"""The hybrid decoder-decoder model (models/hybrid.py) against the plain
reference (benchmark/reference/sambay_hybrid.py), which shares no code
with it: per kind of layer and whole, logits, loss and gradients; remat on
and off; the layer order; ShardedTrainStep on the new config under fsdp=2.

Tolerances.  Both sides compute in float32 here (`dtype=float32`, the
matmuls at "highest"), so what separates them is summation order: 2e-4 on
logits of magnitude ~4 and on gradients relative to each leaf's largest
entry (measured: 1.3e-5 and 8e-6).  With `fused_ce` the head's operands
are rounded to bfloat16 inside ops/fused_ce.py whatever the model's dtype,
so a logit carries a relative error of 2^-9: the loss takes the tolerance
benchmark/tests/test_reference.py argues for the dense model, 2e-3
(measured here: up to 3.4e-4).  Its backward rounds softmax - onehot and
the hidden states to bfloat16 as well, and that error reaches every
parameter's gradient: 5e-2 of a leaf's largest entry (measured: 0.5 to
1.6e-2 over three seeds), where a wrong term in any layer moves it by
tenths.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import sambay_hybrid as ref
from ray_tpu.models import hybrid as hy

ALL_FIVE = ("mamba", "window", "mamba", "window", "mamba", "full", "gmu",
            "cross")


def _config(kinds=ALL_FIVE, **kw):
    return hy.HybridConfig.tiny(dtype=jnp.float32, layer_kinds=kinds, **kw)


def _dims(c):
    return ref.dims_from_config({
        "num_attention_heads": c.num_attention_heads,
        "num_key_value_heads": c.num_key_value_heads,
        "hidden_size": c.hidden_size, "sliding_window": c.sliding_window,
        "layer_norm_eps": c.layer_norm_eps,
        "mamba_d_state": c.mamba_d_state, "mamba_dt_rank": c.mamba_dt_rank,
        "layer_kinds": list(c.layer_kinds)})


def _params(c, seed=0):
    """The program's init, then every leaf moved: with norms at 1, biases
    near 0 and D at 1 a wrong bias or norm would go unseen.  (Eager on
    purpose: a leaf's draw is cached by its shape across cases, where ONE
    jitted program of all the draws costs five seconds a configuration.)"""
    params = hy.init_params(c, jax.random.key(seed))
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    return jax.tree.unflatten(treedef, [
        l + 0.05 * jax.random.normal(k, l.shape)
        for l, k in zip(leaves, keys)])


# The program's entry points, each ONE jitted function for the file: cases
# that call one with an equal configuration share its compile, and a call
# dispatched primitive by primitive compiles its layer scans anew each time.
_forward = jax.jit(hy.forward, static_argnums=2)
_loss = jax.jit(hy.loss_fn, static_argnums=2)
_nll = jax.jit(hy.token_nll, static_argnums=2)


def _tokens(c, rows=2, seq=40, seed=0):
    return np.random.default_rng(seed).integers(
        0, c.vocab_size, (rows, seq + 1)).astype(np.int32)


def _ref_logits(params, tokens, c):
    return jnp.stack([ref.logits(params, row[:-1], _dims(c))
                      for row in tokens])


# one kind of layer at a time: the readers need their source before them
_ONE_KIND = {"mamba": ("mamba",), "window": ("window",), "full": ("full",),
             "gmu": ("mamba", "gmu"), "cross": ("full", "cross")}


@pytest.mark.parametrize("kind", sorted(_ONE_KIND))
def test_each_kind_of_layer_matches_the_reference(kind):
    c = _config(_ONE_KIND[kind])
    params, tokens = _params(c, seed=3), _tokens(c, seq=48)
    with jax.default_matmul_precision("highest"):
        got = _forward(params, jnp.asarray(tokens[:, :-1]), c)
        want = _ref_logits(params, tokens, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_whole_model_logits_and_loss_match_the_reference():
    c = _config()
    params, tokens = _params(c), _tokens(c)
    with jax.default_matmul_precision("highest"):
        got = _forward(params, jnp.asarray(tokens[:, :-1]), c)
        want = _ref_logits(params, tokens, c)
        loss = _loss(params, {"tokens": jnp.asarray(tokens)}, c)
        ref_loss = ref.loss(params, tokens, _dims(c))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)
    assert abs(float(loss) - float(ref_loss)) < 2e-4
    assert 4.0 < float(ref_loss) < 8.0      # near log(256): random weights


@pytest.mark.parametrize("fused_ce", [False, True])
def test_token_nll_is_what_the_loss_averages_and_matches_the_reference(
        fused_ce):
    """`token_nll` [b, s] is the loss before the mean (under a mask too)
    and, position by position, the reference's: the chip's `correct`
    compares the two per token, because with random weights the mean is
    log(vocabulary) + noise whatever the layers compute."""
    c = _config(fused_ce=fused_ce)
    params, tokens = _params(c, seed=9), _tokens(c, seed=9)
    batch = {"tokens": jnp.asarray(tokens)}
    with jax.default_matmul_precision("highest"):
        nll = _nll(params, batch, c)
        want = ref.batch_token_nll(params, tokens, _dims(c))
        assert nll.shape == want.shape == (2, 40) and nll.dtype == jnp.float32
        assert float(jnp.mean(nll)) == pytest.approx(
            float(_loss(params, batch, c)), abs=1e-6)
        mask = jnp.asarray(np.random.default_rng(1).integers(0, 2, (2, 41)),
                           jnp.float32)
        masked = _loss(params, {**batch, "mask": mask}, c)
        assert float(masked) == pytest.approx(
            float(jnp.sum(nll * mask[:, 1:]) / jnp.sum(mask[:, 1:])), abs=1e-6)
        assert float(ref.loss(params, tokens, _dims(c))) == pytest.approx(
            float(jnp.mean(want)), abs=1e-6)
    # float32: 2e-4; the fused head rounds its operands to bfloat16: a
    # logit of magnitude 4 moves by up to 4 x 2^-8, and so may one NLL
    np.testing.assert_allclose(np.asarray(nll), np.asarray(want),
                               atol=2e-2 if fused_ce else 2e-4)


@pytest.mark.parametrize("fused_ce,tol,grad_tol", [(False, 2e-4, 2e-4),
                                                   (True, 2e-3, 5e-2)])
def test_loss_and_gradients_match_the_reference(fused_ce, tol, grad_tol):
    c = _config(fused_ce=fused_ce)
    params, tokens = _params(c, seed=5), _tokens(c, seed=5)
    batch = {"tokens": jnp.asarray(tokens)}
    with jax.default_matmul_precision("highest"):
        # each gradient under ONE jit: dispatched primitive by primitive the
        # two make 702 compilations
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: hy.loss_fn(p, batch, c)))(params)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, tokens, _dims(c))))(params)
    assert abs(float(loss) - float(ref_loss)) < tol
    flat, _ = jax.tree_util.tree_flatten_with_path(
        jax.tree.map(lambda g, r: float(jnp.abs(g - r).max()
                                        / (jnp.abs(r).max() + 1e-12)),
                     grads, ref_grads))
    worst = max(flat, key=lambda kv: kv[1])
    assert worst[1] < grad_tol, jax.tree_util.keystr(worst[0])
    assert all(float(jnp.abs(r).max()) > 0 for r in jax.tree.leaves(ref_grads))


def test_recurrence_alone_on_the_references_operands_gives_its_memory():
    """The chip's `correct` hands the reference's operands of the memory
    source's recurrence to the program's `recurrence` (what the mamba mixer
    calls): with no other layer's rounding beside it, a recurrence that is
    not float32 stands out.  Here in float32 the two agree; and the
    operands are what the reference's own layer made its memory from."""
    c = _config()
    params, tokens = _params(c, seed=11), _tokens(c, rows=1, seed=11)
    with jax.default_matmul_precision("highest"):
        run = ref.Pass(params, tokens[0, :-1], _dims(c))
        operands, want = run.recurrence()
        got = hy.recurrence(*operands, config=c)
    assert got.shape == want.shape == (1, 40, c.d_inner)
    assert operands[0].shape == operands[1].shape == (1, 40, c.d_inner)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert float(jnp.abs(want).max()) > 0.1
    # a bfloat16 state is seen: far above the 2e-5 of the sound one
    low = jnp.bfloat16
    from ray_tpu.ops import selective_scan as ss
    x, dt, a_log, b_t, c_t, d = operands
    rough = ss.selective_scan_xla(
        x.astype(low), dt.astype(low), -jnp.exp(a_log).astype(low),
        b_t.astype(low), c_t.astype(low), d.astype(low))
    assert float(jnp.sqrt(jnp.mean((rough.astype(jnp.float32) - want) ** 2)
                          / jnp.mean(want ** 2))) > 2e-3


@pytest.mark.parametrize("policy", ["full", "dots"])
def test_remat_on_and_off_give_the_same_loss_and_gradients(policy):
    """The memory and the shared KV cross per-layer remat as arguments
    and results of the checkpointed layers: nothing changes in the
    answer."""
    on = _config(remat=True, remat_policy=policy)
    off = dataclasses.replace(on, remat=False)
    params, batch = _params(on), {"tokens": jnp.asarray(_tokens(on))}
    l_on, g_on = jax.jit(jax.value_and_grad(
        lambda p: hy.loss_fn(p, batch, on)))(params)
    l_off, g_off = jax.jit(jax.value_and_grad(
        lambda p: hy.loss_fn(p, batch, off)))(params)
    assert float(l_on) == pytest.approx(float(l_off), abs=1e-6)
    for a, b in zip(jax.tree.leaves(g_on), jax.tree.leaves(g_off)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_layer_kinds_of_the_published_model_and_of_the_cut():
    import json
    import os

    from collections import Counter

    full = hy.HybridConfig()
    assert full.num_layers == 32
    assert Counter(full.layer_kinds) == {"mamba": 9, "window": 8, "full": 1,
                                         "gmu": 7, "cross": 7}
    assert full.memory_source == 16 and full.layer_kinds[17] == "full"
    # the published 32 layers scan as two runs and do not unroll
    assert hy.segments(full) == [(("mamba", "window"), 0, 8),
                                 (("mamba",), 16, 1), (("full",), 17, 1),
                                 (("gmu", "cross"), 18, 7)]
    assert hy.num_params(full) == 3_852_562_944     # the model card's 3.8 B
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "configs",
                        "phi4-mini-flash-train-d8.json")
    cut = json.load(open(path))["model"]["layer_kinds"]
    assert set(cut) == set(hy.KINDS) and len(cut) == 8
    # a sub-list of the published order ...
    it = iter(full.layer_kinds)
    assert all(kind in it for kind in cut)
    # ... that keeps both sources before their readers
    last_mamba = max(i for i, k in enumerate(cut) if k == "mamba")
    assert last_mamba < cut.index("gmu") and cut.index("full") < cut.index(
        "cross")
    assert hy.num_params(hy.HybridConfig(
        vocab_size=25008, layer_kinds=cut)) == 915_311_616


@pytest.mark.parametrize("kinds,why", [
    (("gmu", "mamba"), "no mamba layer before"),
    (("mamba", "cross"), "no full layer before"),
    (("mamba", "linear"), "unknown layer kinds")])
def test_a_reader_before_its_source_is_refused(kinds, why):
    with pytest.raises(ValueError, match=why):
        hy.HybridConfig.tiny(layer_kinds=kinds)


def test_logical_axes_match_the_parameters():
    c = _config()
    params = jax.eval_shape(lambda: hy.init_params(c, jax.random.key(0)))
    axes = hy.logical_axes(c)
    is_axes = lambda v: isinstance(v, tuple)     # noqa: E731
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=is_axes)
    for p, a in zip(jax.tree.leaves(params),
                    jax.tree.leaves(axes, is_leaf=is_axes)):
        assert p.ndim == len(a), (p.shape, a)
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert n == hy.num_params(c)


def test_sharded_train_step_takes_the_model_from_its_config_under_fsdp2():
    """ShardedTrainStep on a HybridConfig, two host devices, fsdp=2: the
    parameters with an `embed` axis are sharded, the state-space ones
    without are whole, and the loss falls."""
    from ray_tpu.models import transformer as tfm
    from ray_tpu.parallel.mesh import build_mesh
    from ray_tpu.train.train_state import ShardedTrainStep, default_optimizer

    c = hy.HybridConfig.tiny(fused_ce=True)
    mesh = build_mesh(axes={"fsdp": 2}, devices=jax.devices()[:2])
    ts = ShardedTrainStep(c, mesh, optimizer=default_optimizer(
        learning_rate=3e-3, warmup_steps=1, total_steps=50,
        mu_dtype=jnp.bfloat16, nu_dtype=jnp.bfloat16))
    assert ts.model is hy
    assert ShardedTrainStep(tfm.TransformerConfig.tiny(), mesh).model is tfm
    state = ts.init(jax.random.key(0))
    seg = state["params"]["layers"]["seg00"]["0"]
    assert {s.data.shape for s in seg["in_proj"].addressable_shards} == {
        (2, c.hidden_size // 2, 2 * c.d_inner)}
    assert seg["A_log"].addressable_shards[0].data.shape == (
        2, c.d_inner, c.mamba_d_state)
    batch = {"tokens": jnp.asarray(_tokens(c, rows=4, seq=32))}
    losses = []
    for _ in range(6):
        state, metrics = ts.step(state, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.1, losses
    assert abs(float(ts.eval_step(state["params"], batch)) - losses[-1]) < 1.0
    per_token = ts.eval_step(state["params"], batch, output="token_nll")
    assert per_token.shape == (4, 32)
    assert float(jnp.mean(per_token)) == pytest.approx(
        float(ts.eval_step(state["params"], batch)), abs=2e-3)


@pytest.mark.parametrize("moments,tol", [(jnp.float32, 1e-5),
                                         (jnp.bfloat16, 5e-3)])
def test_the_benchmarks_driver_reads_the_first_steps_gradient_from_adam(
        moments, tol):
    """benchmark/drivers/train_model.py holds the gradient the timed step
    program itself computed against the reference's.  It reads it from
    the first moment the FIRST step leaves ((1 - b1) x the clipped
    gradient, the clip undone by the step's `grad_norm`): here against
    `jax.grad` of the same loss (the clip is active: the norm is over 1),
    and against a gradient with one parameter halved, which must show as
    that parameter's."""
    from benchmark.drivers import train_model
    from ray_tpu.parallel.mesh import build_mesh
    from ray_tpu.train.train_state import ShardedTrainStep, default_optimizer

    c = _config(fused_ce=False)
    mesh = build_mesh(axes={"fsdp": 1}, devices=jax.devices()[:1])
    ts = ShardedTrainStep(c, mesh, optimizer=default_optimizer(
        warmup_steps=2, total_steps=50, b1=train_model.ADAM_B1,
        grad_clip=train_model.GRAD_CLIP, mu_dtype=moments, nu_dtype=moments))
    state = ts.init(jax.random.key(0))
    tokens = _tokens(c, rows=2, seq=32)
    batch = {"tokens": jnp.asarray(tokens)}
    want = jax.jit(jax.grad(lambda p: hy.loss_fn(p, batch, c)))(
        state["params"])

    def as_the_reference_gives_them(grads, halve=None):
        out = {}
        for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
            keys = tuple(k.key for k in path)
            if keys[0] != "layers":
                out[keys, None] = np.asarray(g)
                continue
            for rep in range(g.shape[0]):
                out[keys[:3] + (rep,), keys[3]] = np.asarray(g[rep]) * (
                    0.5 if (keys[1], keys[2], keys[3], rep) == halve else 1)
        return out

    state, metrics = ts.step(state, batch)
    norm = float(metrics["grad_norm"])
    assert norm > train_model.GRAD_CLIP
    got = train_model.first_step_gradient(
        state, norm, as_the_reference_gives_them(want))
    worst = train_model.worst_parameter(got["by_leaf"])
    assert got["rel"] < tol and got["by_leaf"][worst] < 4 * tol, got
    assert got["reference_norm"] == pytest.approx(norm, rel=1e-4)
    broken = train_model.first_step_gradient(
        state, norm, as_the_reference_gives_them(
            want, halve=("seg00", "0", "A_log", 1)))
    worst = train_model.worst_parameter(broken["by_leaf"])
    assert worst == "layers/seg00/0/1/A_log"
    assert broken["by_leaf"][worst] == pytest.approx(1.0, rel=0.02)
    assert train_model.worst_parameter(
        broken["by_leaf"], excludes=["A_log"]) != worst


# LAST in the file: it clears JAX's caches round itself, and every case behind
# it would compile its draws and entry points again.  The five kinds once
# each (the memory and the shared KV have ONE reader), then a second (gmu,
# cross) pair (two readers, and a scanned pair's second repeat), then no
# reader at all
ONE_READER = ("mamba", "window", "full", "gmu", "cross")


@pytest.mark.parametrize("kinds", [ONE_READER, ONE_READER + ("gmu", "cross"),
                                   ("mamba", "window", "mamba")])
def test_the_references_layer_by_layer_gradient_is_its_jax_grad(
        kinds, monkeypatch):
    """`Pass.grads` walks the reference back one layer at a time (so that
    8192 tokens fit beside a train state); it must give what `jax.grad` of
    the reference's loss gives, for every parameter, with the memory and
    the shared KV read by none, one or two layers, and with chunks and
    blocks shorter than the sequence."""
    monkeypatch.setattr(ref, "SCAN_CHUNK", 16)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    monkeypatch.setattr(ref, "LOGIT_ROWS", 16)
    jax.clear_caches()      # the reference's jitted layers read the sizes
    c = _config(kinds)
    params, tokens = _params(c, seed=13), _tokens(c, rows=1, seed=13)
    want = jax.jit(jax.grad(
        lambda p: ref.loss(p, tokens, _dims(c))))(params)
    run = ref.Pass(params, tokens[0, :-1], _dims(c), for_grads=True)
    seen = 0
    for path, grad in run.grads(tokens[0, 1:]):
        if path[0] == "layers":
            _, seg, pos, rep = path
            pairs = [(g, want["layers"][seg][pos][name][rep])
                     for name, g in grad.items()]
        else:
            pairs = [(grad, want[path[0]])]
        for g, w in pairs:
            seen += 1
            assert float(jnp.linalg.norm(g - w)) <= 1e-4 * float(
                jnp.linalg.norm(w)) + 1e-7, path
    # every parameter of every layer (a stacked leaf holds one a repeat)
    assert seen == 3 + sum(leaf.shape[0]
                           for leaf in jax.tree.leaves(want["layers"]))
    jax.clear_caches()
