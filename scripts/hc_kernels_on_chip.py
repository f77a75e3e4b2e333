"""ops/hyper_connection.py's four kernels on the chip, alone: each against
the step-at-a-time reference on seeded operands at a cell's sizes, then its
time a call.  One JSON object a line; the last says `ok`.

    python scripts/hc_kernels_on_chip.py [--rows 1] [--seq 8192] [--hidden 3584]

`probe_f32` is the model's probe (`latent_moe.residual_mix`: both forward
kernels round the identity, u and X' left in float32) against the reference's
float32; under `PYTHONPATH=scripts/hc_breaks HC_BREAK=<name>` the kernels are
the broken ones and it reads what the break leaves of it.
"""

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from ray_tpu.ops import hyper_connection as H  # noqa: E402

F32 = jnp.float32


def rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def timed(fn, *args, repeats=10):
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / repeats * 1e3


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--hidden", type=int, default=3584)
    ap.add_argument("--lanes", type=int, default=4)
    args = ap.parse_args()
    from types import SimpleNamespace

    from ray_tpu.models import stack

    # the settings as a model's config gives them (`HC_BREAK=one_round`
    # breaks them there)
    hc = stack.hyper(SimpleNamespace(
        hc_mult=args.lanes, hc_sinkhorn_iters=20, hc_eps=1e-6,
        rms_norm_eps=1e-6, mhc_h_res_clamp_min=-30.0,
        mhc_h_res_clamp_max=30.0))
    sound = H.HC(args.lanes)        # the reference's
    b, s, d, n = args.rows, args.seq, args.hidden, args.lanes
    k = jax.random.split(jax.random.key(7), 8)
    x = jax.random.normal(k[0], (b, s, n * d)).astype(jnp.bfloat16)
    w = jax.random.normal(k[1], (n * d, hc.width)) / np.sqrt(n * d)
    scale = jnp.array([0.5, 0.5, 1.0])
    base = jnp.concatenate([jnp.full((n,), -np.log(n - 1.0)), jnp.zeros((n,)),
                            2.0 * jnp.eye(n).reshape(-1)])
    y = jax.random.normal(k[2], (b, s, d)).astype(jnp.bfloat16)
    gu = jax.random.normal(k[3], (b, s, d)).astype(jnp.bfloat16)
    gx = jax.random.normal(k[4], (b, s, n * d)).astype(jnp.bfloat16)
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "path": H.path(x, hc), "shape": list(x.shape)}))
    if H.path(x, hc) == "xla":
        print(json.dumps({"ok": False, "why": "the kernels are not taken"}))
        return 1

    def both(pre, post):
        def fn(x, w, scale, base, y):
            u, mix = pre(x, w, scale, base, hc)[:2]
            out = post(x, y, mix, hc)
            return (jnp.sum(out.astype(F32) * gx.astype(F32))
                    + jnp.sum(u.astype(F32) * gu.astype(F32))), (u, mix, out)
        return jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2, 3, 4),
                                          has_aux=True))

    (_, got), g_got = both(H.hc_pre, H.hc_post)(x, w, scale, base, y)
    with jax.default_matmul_precision("highest"):
        (_, want), g_want = both(
            lambda *a: H.hc_pre_reference(*a[:-1], sound),
            lambda *a: H.hc_post_reference(*a[:-1], sound))(
            x, w, scale, base, y)
    errors = {name: rel(a, r) for name, a, r in zip(
        ("u", "mix", "x_out"), got, want)}
    errors.update({"d" + name: rel(a, r) for name, a, r in zip(
        ("x", "w", "scale", "base", "y"), g_got, g_want)})
    for name, a, r in zip(("pre", "post", "comb"), H.mix_parts(got[1], n),
                          H.mix_parts(want[1], n)):
        errors["mix_" + name] = rel(a, r)

    def probe(pre, post):
        u, mix = pre(x, w, scale, base, hc, out_dtype=F32)[:2]
        return post(x, u, mix, hc, out_dtype=F32)

    with jax.default_matmul_precision("highest"):
        exact = jax.jit(lambda: probe(
            lambda *a, **k: H.hc_pre_reference(*a[:-1], sound, **k),
            lambda *a, **k: H.hc_post_reference(*a[:-1], sound, **k)))()
    errors["probe_f32"] = rel(jax.jit(lambda: probe(H.hc_pre, H.hc_post))(),
                              exact)
    comb = H.mix_parts(got[1], n)[2]
    errors["comb_row_sum_off"] = float(jnp.abs(comb.sum(-1) - 1).max())
    print(json.dumps({"relative_errors": errors}))
    mix = got[1]
    ms = {
        "hc_pre_fwd": timed(lambda: H._pre_forward(x, w, scale, base, hc)),
        "hc_post_fwd": timed(lambda: H._post_forward(x, y, mix, hc)),
        "hc_post_bwd": timed(jax.jit(
            lambda g: H._post_backward(x, y, mix, g * 1, hc)), gx),
        "hc_pre_bwd": timed(jax.jit(lambda g: H._pre_backward(
            x, w, scale, base, gu, mix, g * 1, hc)), gx),
    }
    stream = x.size * 2
    least = {"hc_pre_fwd": stream * 1.25, "hc_post_fwd": stream * 2.25,
             "hc_post_bwd": stream * 3.5, "hc_pre_bwd": stream * 3.25}
    print(json.dumps({"ms_a_call": ms, "GB_per_s_at_least_bytes": {
        name: least[name] / (v * 1e-3) / 1e9 for name, v in ms.items()}}))
    ok = all(v < 0.03 for v in errors.values())
    print(json.dumps({"ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
