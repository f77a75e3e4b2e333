"""Attention benchmark artifact (VERDICT r3 item 7).

Writes ONE JSON document to stdout with:
  - flash vs naive (dense XLA) attention on the REAL chip, fwd and
    fwd+bwd, at the headline train shape (b8 s2048) and the
    long-context shape (b2 s8192) — the naive path materializes the
    [s, s] score matrix in HBM, the Pallas flash kernel never does;
  - ring-attention step time over the 8-virtual-device CPU mesh
    (sequence-parallel ppermute ring; correctness is pinned by
    tests/test_ops_attention.py — the CPU wall time only demonstrates
    the sharded program executes end-to-end and scales by ring step,
    not kernel speed).

Run: python scripts/bench_attention_artifact.py > ATTN_BENCH_rNN.json
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time_chained(step_fn, carry0, steps=50):
    """Time steps that CHAIN on device INSIDE one jitted fori_loop.

    A Python-level chain (one dispatch per step) adds host dispatch
    latency to every ms-scale kernel.  Running the whole chain as one
    device program and subtracting an empty-loop control of the same
    trip count isolates the kernel."""
    import jax
    from jax import lax

    @jax.jit
    def run(c):
        return lax.fori_loop(0, steps, lambda i, c: step_fn(c), c)

    @jax.jit
    def empty(c):
        return lax.fori_loop(
            0, steps,
            lambda i, c: jax.tree.map(lambda x: x * (1 + 1e-7), c), c)

    jax.block_until_ready(run(carry0))    # compile
    jax.block_until_ready(empty(carry0))
    tb = te = 1e9
    for _ in range(4):
        t0 = time.perf_counter()
        jax.block_until_ready(empty(carry0))
        te = min(te, time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(run(carry0))
        tb = min(tb, time.perf_counter() - t0)
    return max((tb - te) / steps, 1e-9)


def chip_rows():
    import jax
    import jax.numpy as jnp

    from bench import _peak_flops
    from ray_tpu.ops.attention import attention_reference, flash_attention

    peak = _peak_flops(jax.devices()[0])
    rows = []
    for b, s, h, d in ((8, 2048, 14, 128), (2, 8192, 14, 128)):
        key = jax.random.key(0)
        q = jax.random.normal(key, (b, s, h, d), dtype=jnp.bfloat16)
        k = jax.random.normal(key, (b, s, h, d), dtype=jnp.bfloat16)
        v = jax.random.normal(key, (b, s, h, d), dtype=jnp.bfloat16)
        causal_flops = 2 * b * h * s * s * d  # fwd, lower triangle x2 mms

        def fwd_step_of(f):
            # Chain the output back in as q: same shape/dtype, forces
            # sequential device execution with no host transfers.
            return jax.jit(lambda qq: f(qq, k, v))

        def bwd_step_of(f):
            loss = lambda q, k, v: f(q, k, v).astype(  # noqa: E731
                jnp.float32).sum()
            g = jax.grad(loss, argnums=(0, 1, 2))
            return jax.jit(lambda qq: g(qq, k, v)[0])  # dq chains as q

        flash = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, causal=True, block_q=512, block_k=512)
        naive = lambda q, k, v: attention_reference(  # noqa: E731
            q, k, v, causal=True)

        naive_steps = 4 if s >= 4096 else 20  # dense s8192 is ~1.5 s/step
        row = {"shape": f"b{b} s{s} h{h} d{d}"}
        t = _time_chained(fwd_step_of(flash), q)
        row["flash_fwd_ms"] = round(t * 1e3, 2)
        row["flash_fwd_flops_frac"] = round(causal_flops / t / peak, 3)
        try:
            t = _time_chained(fwd_step_of(naive), q, steps=naive_steps)
            row["naive_fwd_ms"] = round(t * 1e3, 2)
            row["speedup_fwd"] = round(
                row["naive_fwd_ms"] / row["flash_fwd_ms"], 2)
        except Exception as e:  # noqa: BLE001 — dense s=8192 can OOM
            row["naive_fwd_ms"] = f"OOM: {type(e).__name__}"
        t = _time_chained(bwd_step_of(flash), q, steps=25)
        row["flash_fwd_bwd_ms"] = round(t * 1e3, 2)
        row["flash_fwd_bwd_flops_frac"] = round(
            3.5 * causal_flops / t / peak, 3)
        try:
            t = _time_chained(bwd_step_of(naive), q, steps=naive_steps)
            row["naive_fwd_bwd_ms"] = round(t * 1e3, 2)
            row["speedup_fwd_bwd"] = round(
                row["naive_fwd_bwd_ms"] / row["flash_fwd_bwd_ms"], 2)
        except Exception as e:  # noqa: BLE001
            row["naive_fwd_bwd_ms"] = f"OOM: {type(e).__name__}"
        rows.append(row)
    return rows


_RING_CHILD = r"""
import os, sys, time, json
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, %(root)r)
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.parallel.mesh import build_mesh

b, s, h, d = 2, 2048, 4, 64
key = jax.random.key(0)
q = jax.random.normal(key, (b, s, h, d), jnp.float32)
k = jax.random.normal(key, (b, s, h, d), jnp.float32)
v = jax.random.normal(key, (b, s, h, d), jnp.float32)
out = {}
for n_seq in (1, 2, 4, 8):
    mesh = build_mesh(axes={"seq": n_seq},
                      devices=jax.devices()[:n_seq])
    f = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh=mesh,
                                               causal=True))
    o = f(q, k, v); np.asarray(o)
    t0 = time.perf_counter()
    for _ in range(5):
        o = f(q, k, v)
    np.asarray(o)
    out[f"seq={n_seq}"] = round((time.perf_counter() - t0) / 5 * 1e3, 1)
print(json.dumps(out))
"""


def ring_rows():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_"))}
    env["RAY_TPU_CHIPS"] = "none"
    # The child is CPU-virtual; this parent holds the chip.
    env["JAX_PLATFORMS"] = "cpu"
    try:
        res = subprocess.run(
            [sys.executable, "-c", _RING_CHILD % {"root": root}],
            capture_output=True, text=True, timeout=900, env=env)
    except subprocess.TimeoutExpired:
        # The chip measurements already collected must still be
        # emitted; a slow/loaded host only costs the ring section.
        return {"error": "ring child timed out (900s)"}
    if res.returncode != 0:
        return {"error": res.stderr[-500:]}
    return json.loads(res.stdout.strip().splitlines()[-1])


def main():
    import jax

    doc = {
        "metric": "attention_bench",
        "device": getattr(jax.devices()[0], "device_kind",
                          jax.devices()[0].platform),
        "chip": chip_rows(),
        "ring_attention_cpu_mesh_step_ms": ring_rows(),
        "note": ("flash = in-tree Pallas kernel (ops/attention.py), "
                 "naive = dense XLA reference materializing [s,s] "
                 "scores; timing = on-device fori_loop chain minus an "
                 "empty-loop control (a Python-level chain pays host "
                 "dispatch per step); ring rows time one "
                 "jitted step of sequence-parallel ring attention "
                 "(ops/ring_attention.py) on an n-device virtual CPU "
                 "mesh at fixed GLOBAL shape b2 s2048 h4 d64"),
    }
    print(json.dumps(doc))
    return 0


if __name__ == "__main__":
    sys.exit(main())
