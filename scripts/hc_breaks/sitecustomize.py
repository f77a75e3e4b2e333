"""The builder's tool for setting `train-mhc-mla-moe-d5`'s tolerances: one
deliberate break of the program (or, `reference_bf16`, the reference in the
precision below), in the worker that holds the chip, chosen by the
environment variable HC_BREAK; never on a path the benchmark runs.

    PYTHONPATH=scripts/hc_breaks HC_BREAK=one_round python3 benchmark/run.py \
        --workload train-mhc-mla-moe-d5 --seed <n> --seconds 20 --trace 0

A TPU-class worker inherits the driver's environment and imports `site`, so
this module runs there before the train loop; it patches the program after
import, by name.  The run's `phase: train` line has the readings
(`reference_token_rms`, `reference_probe_rel`, `gradient`), and `correct`
says which limit the break failed by."""

import importlib
import os
import sys

BREAK = os.environ.get("HC_BREAK", "")


def _apply():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import latent_moe, moe, stack
    from ray_tpu.ops import hyper_connection as H

    bf16 = jnp.bfloat16
    if BREAK == "bf16_router":
        def route(x, router_w, select_bias, *, num_experts_per_token, scale):
            scores = jax.nn.sigmoid(jnp.dot(
                x.astype(bf16), router_w.astype(bf16),
                preferred_element_type=jnp.float32))
            return moe.select_experts(
                scores, select_bias,
                num_experts_per_token=num_experts_per_token,
                gate_rule="renormalised", scale=scale)
        moe.sigmoid_route = route
    elif BREAK == "bf16_rounds":
        sound = H._mix_rows

        def rounds(z, hc):
            n, eps = hc.n, hc.eps
            pre, post, *m = sound(z, hc._replace(iters=1))

            def low(v):
                return v.astype(bf16).astype(jnp.float32)

            m = [low(row) for row in m]
            for _ in range(hc.iters - 1):
                m = [low(row / (jnp.sum(row, -2, keepdims=True) + eps))
                     for row in m]
                sums = sum(m) + eps
                m = [low(row / sums) for row in m]
            return [pre, post, *m]
        H._mix_rows = rounds
    elif BREAK == "one_round":
        sound = stack.hyper
        stack.hyper = lambda config: sound(config)._replace(iters=1)
    elif BREAK == "no_mtp_loss":
        latent_moe.MTP_LOSS_WEIGHT = 0.0
    elif BREAK == "no_yarn_scale":
        latent_moe.LatentMoEConfig.softmax_scale = property(
            lambda self: 1.0 / self.qk_head_dim ** 0.5)
    elif BREAK == "transposed_comb":
        sound = H.hc_post

        def post(x, y, mix, hc, out_dtype=None):
            n = hc.n
            comb = mix[..., 2 * n:hc.width].reshape(*mix.shape[:-1], n, n)
            flipped = jnp.swapaxes(comb, -1, -2).reshape(*mix.shape[:-1], -1)
            return sound(x, y, jnp.concatenate(
                [mix[..., :2 * n], flipped, mix[..., hc.width:]], -1), hc,
                out_dtype)
        H.hc_post = post
    elif BREAK == "reference_bf16":
        # not the program: the plain REFERENCE computed in the nearest
        # precision below the configuration's (every array of the pass in
        # bfloat16: weights, stream, rounds, router scores, softmax and
        # loss statistics), which has to come out as not correct
        from benchmark.reference import deepseek_v3_mla_moe, xing4_hc_mla_moe
        deepseek_v3_mla_moe.F32 = xing4_hc_mla_moe.F32 = bf16
        sound = xing4_hc_mla_moe.Pass.token_nll
        # .. and handed over in float32: the driver's mean is numpy's, and
        # numpy sums bfloat16 in bfloat16
        xing4_hc_mla_moe.Pass.token_nll = lambda self, targets: sound(
            self, targets).astype(jnp.float32)
    else:
        raise SystemExit(f"HC_BREAK={BREAK!r}: no such break")
    print(f"hc_breaks: the program is broken on purpose: {BREAK}",
          file=sys.stderr, flush=True)


if BREAK and importlib.util.find_spec("ray_tpu") is not None:
    _apply()
