"""The program against the plain reference, at tiny size on the CPU.

Both sides run in float32 here, so they compute the same mathematics in
a different order of summation (the program batches, pads, scans layers
and decodes through a paged cache; the reference does none of that).
Tolerance: 2e-4 absolute on logits whose spread is about 1 and on a loss
near 5.5: float32 rounding through two layers is some 1e-6 to 1e-5, and
anything that changes the mathematics (a wrong position in RoPE, a mask
off by one, a page misread, bfloat16 anywhere) moves a logit by 1e-2 or
more.  On the chip the served model is bfloat16 and the tolerance is the
configuration's (`reference_check`), with its own reason."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import dense_rope_swiglu as ref

MODEL = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
         "vocab_size": 256, "max_position_embeddings": 256,
         "rope_theta": 130000, "rms_norm_eps": 1e-5}
TOL = 2e-4


def _cfg(**kw):
    from ray_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=4, head_dim=16, max_seq_len=256,
        rope_theta=130000.0, rms_eps=1e-5, dtype=jnp.float32,
        param_dtype=jnp.float32, **kw)


@pytest.fixture(scope="module")
def params():
    from ray_tpu.models import transformer as tfm

    return tfm.init_params(_cfg(), jax.random.key(3))


def test_prefill_then_decode_through_the_cache_agrees(params):
    """Served path: prefill writes the pages, decode reads them; at every
    generated position the logits equal the reference's full forward
    pass over prompt + generated tokens."""
    from ray_tpu.models import decoding

    cfg = _cfg(remat=False)
    dims = ref.dims_from_config(MODEL)
    page, n_pages, plen, new = 16, 16, 37, 9
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 256, plen).astype(np.int32)
    cache = decoding.init_kv_pages(cfg, n_pages, page)
    table = np.zeros((1, n_pages), np.int32)
    table[0, :4] = [3, 7, 1, 9]
    S = 64
    tokens = np.zeros((1, S), np.int32)
    tokens[0, :plen] = prompt
    positions = np.full((1, S), -1, np.int32)
    positions[0, :plen] = np.arange(plen)
    logits, cache = decoding.prefill(
        params, jnp.asarray(tokens), jnp.asarray(positions), cache,
        jnp.asarray(table), cfg)
    seq = list(prompt)
    served = [np.asarray(logits)[0]]
    for i in range(new - 1):
        tok = int(np.argmax(served[-1]))
        seq.append(tok)
        pos = len(seq) - 1
        lg, cache = decoding.decode_step(
            params, jnp.asarray([tok], jnp.int32), cache, jnp.asarray(table),
            jnp.asarray([pos], jnp.int32), jnp.asarray([pos + 1], jnp.int32),
            cfg)
        served.append(np.asarray(lg)[0])
    want = np.asarray(ref.logits(params, seq, dims))[plen - 1:]
    got = np.stack(served)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) < TOL
    assert np.std(want) > 0.3  # the logits are not all alike


def test_loss_fn_agrees(params):
    from ray_tpu.models import transformer as tfm

    tokens = np.random.default_rng(1).integers(0, 256, (3, 65)).astype(np.int32)
    want = ref.loss(params, tokens, ref.dims_from_config(MODEL))
    # The fused chunked cross-entropy rounds its operands to bfloat16
    # whatever the configuration's dtype (ops/fused_ce.py), so the head's
    # logits carry a relative error of 2^-9; on a loss near 6 that is up
    # to some 1e-3, and a wrong target shift or mask still moves it by
    # tenths.
    for kw, tol in (({"fused_ce": False}, TOL),
                    ({"fused_ce": True, "remat_policy": "full"}, 2e-3)):
        got = float(tfm.loss_fn(params, {"tokens": jnp.asarray(tokens)},
                                _cfg(**kw)))
        assert abs(got - want) < tol, (kw, got, want)


def test_reference_is_causal_and_position_aware(params):
    dims = ref.dims_from_config(MODEL)
    seq = list(np.random.default_rng(2).integers(1, 256, 20))
    a = np.asarray(ref.logits(params, seq, dims))
    b = np.asarray(ref.logits(params, seq[:12] + [5] * 8, dims))
    assert np.allclose(a[:12], b[:12], atol=1e-6)   # the future is unseen
    assert not np.allclose(a[12:], b[12:], atol=1e-3)
    swapped = [seq[1], seq[0]] + seq[2:]
    c = np.asarray(ref.logits(params, swapped, dims))
    assert not np.allclose(a[-1], c[-1], atol=1e-4)  # order matters
