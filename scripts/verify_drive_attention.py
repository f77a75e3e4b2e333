"""Verify driver: flash attention bf16-MXU kernel vs dense reference ON CHIP.

Checks (on the TPU):
  1. fwd values match attention_reference within bf16 tolerance,
     at both bench shapes and a decode-style sq<sk shape;
  2. grads (dq, dk, dv) match within tolerance;
  3. the chunked (offset-aware) kernel agrees with the plain one.
"""
import sys

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import (attention_reference, flash_attention,
                                   flash_attention_chunk)

ok = True


def check(name, a, b, tol):
    global ok
    err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
    scale = float(jnp.max(jnp.abs(b.astype(jnp.float32)))) or 1.0
    rel = err / scale
    status = "OK" if rel < tol else "FAIL"
    if rel >= tol:
        ok = False
    print(f"  {name}: max_abs={err:.4g} rel={rel:.4g} [{status}]")


for b, sq, sk, h, d in ((2, 512, 512, 4, 128), (1, 1024, 1024, 2, 128),
                        (2, 256, 1024, 2, 128)):
    print(f"shape b{b} sq{sq} sk{sk} h{h} d{d}")
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, sk, h, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, sk, h, d), jnp.bfloat16)
    out_f = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=256, block_k=256))(q, k, v)
    out_r = jax.jit(lambda q, k, v: attention_reference(
        q, k, v, causal=True))(q, k, v)
    check("fwd", out_f, out_r, 2e-2)

    if sq == sk:
        def loss_f(q, k, v):
            return flash_attention(q, k, v, causal=True, block_q=256,
                                   block_k=256).astype(jnp.float32).sum()

        def loss_r(q, k, v):
            return attention_reference(
                q, k, v, causal=True).astype(jnp.float32).sum()

        gf = jax.jit(jax.grad(loss_f, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.jit(jax.grad(loss_r, argnums=(0, 1, 2)))(q, k, v)
        for name, a, r in zip(("dq", "dk", "dv"), gf, gr):
            check(name, a, r, 4e-2)

# chunk kernel vs plain (same global positions)
b, s, h, d = 2, 1024, 2, 128
ks = jax.random.split(jax.random.key(1), 3)
q = jax.random.normal(ks[0], (b, s, h, d), jnp.bfloat16)
k = jax.random.normal(ks[1], (b, s, h, d), jnp.bfloat16)
v = jax.random.normal(ks[2], (b, s, h, d), jnp.bfloat16)
out_c, _ = jax.jit(lambda q, k, v: flash_attention_chunk(
    q, k, v, 0, 0, causal=True, block_q=256, block_k=256))(q, k, v)
out_p = jax.jit(lambda q, k, v: flash_attention(
    q, k, v, causal=True, block_q=256, block_k=256))(q, k, v)
print("chunk-vs-plain")
check("chunk", out_c, out_p, 1e-3)

print("ALL OK" if ok else "FAILURES", flush=True)
sys.exit(0 if ok else 1)
