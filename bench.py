"""Headline benchmark: sharded transformer training throughput on TPU.

Prints ONE JSON line:
  {"metric": "train_mfu", "value": <fraction>, "unit": "MFU",
   "vs_baseline": <value / 0.40>, ...}

Baseline: the reference has no in-tree tokens/sec numbers (BASELINE.md —
its LLM release tests are pass/fail); the north-star target recorded in
BASELINE.json is >=40% MFU, so vs_baseline = measured_MFU / 0.40.
"""

from __future__ import annotations

import json
import sys
import time


def _peak_flops(device) -> float:
    """Peak bf16 FLOP/s of one chip, from the repo's one table
    (util/device_stats.PEAK_SPECS).  An unknown kind raises: no MFU is
    computed against an assumed peak."""
    from ray_tpu.util import device_stats

    return device_stats.peak_specs_for(device.device_kind)[1]


def _tpu_config_ladder(tfm):
    """Largest-first configs (billion-class params, seq >= 2048, fsdp
    on); the bench walks down on HBM exhaustion so a run always lands on
    the biggest model the chip holds.

    All rows: seq 2048, head_dim 128 (flash kernel, 512x512 tiles),
    AdamW mu+nu in bf16 (8 B/param of state), fused chunked
    cross-entropy (ops/fused_ce.py), full remat.  MFU on the current
    chip: not measured.  What IS known (PERF.md, PR 25): at batch 8 the
    0.9B step program needs 15.95 GiB against a v5e's 15.75 GiB, so the
    ladder's first row walks down to batch 6 (14.86 GiB) there.
    """
    ladder = []
    ladder.append(("0.9B", tfm.TransformerConfig(
        vocab_size=32000, hidden_size=1792, intermediate_size=7168,
        num_layers=16, num_heads=14, num_kv_heads=14, max_seq_len=2048,
        remat_policy="full", fused_ce=True,
    ), 8, 2048))
    ladder.append(("0.9B-b6", tfm.TransformerConfig(
        vocab_size=32000, hidden_size=1792, intermediate_size=7168,
        num_layers=16, num_heads=14, num_kv_heads=14, max_seq_len=2048,
        remat_policy="full", fused_ce=True,
    ), 6, 2048))
    ladder.append(("0.8B", tfm.TransformerConfig(
        vocab_size=32000, hidden_size=1536, intermediate_size=6144,
        num_layers=20, num_heads=12, num_kv_heads=12, max_seq_len=2048,
        remat_policy="full", fused_ce=True,
    ), 8, 2048))
    ladder.append(("0.5B", tfm.TransformerConfig(
        vocab_size=32000, hidden_size=1536, intermediate_size=6144,
        num_layers=12, num_heads=12, num_kv_heads=12, max_seq_len=2048,
        remat_policy="full", fused_ce=True,
    ), 8, 2048))
    return ladder


def _run_once(config, batch, seq, steps, devices):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import transformer as tfm
    from ray_tpu.parallel.mesh import build_mesh
    from ray_tpu.train.train_state import ShardedTrainStep, default_optimizer

    # fsdp as the device axis: on one chip it is size 1 (pure compute);
    # on a pod slice the same program shards params/opt-state FSDP-style.
    mesh = build_mesh(axes={"fsdp": len(devices)}, devices=devices)
    ts = ShardedTrainStep(
        config, mesh,
        optimizer=default_optimizer(warmup_steps=10, total_steps=1000,
                                    mu_dtype=jnp.bfloat16,
                                    nu_dtype=jnp.bfloat16))
    state = ts.init(jax.random.key(0))

    rng = np.random.default_rng(0)
    batch_np = {
        "tokens": jnp.asarray(
            rng.integers(0, config.vocab_size, (batch, seq + 1)),
            dtype=jnp.int32)
    }

    # warmup / compile; the scalar fetch (float()) is the sync.
    state, metrics = ts.step(state, batch_np)
    float(metrics["loss"])

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = ts.step(state, batch_np)
    final_loss = float(metrics["loss"])
    dt = time.perf_counter() - t0

    tokens = batch * seq * steps
    tok_per_sec = tokens / dt
    flops_tok = tfm.flops_per_token(config, seq)
    if devices[0].platform == "cpu":  # --rehearse: no device metric
        return None, tok_per_sec, final_loss
    peak = _peak_flops(devices[0]) * len(devices)
    mfu = tok_per_sec * flops_tok / peak
    return mfu, tok_per_sec, final_loss


def _long_context_ladder(tfm):
    """seq-8192 rows: the same 0.9B model at 8k context, full remat
    (attention FLOPs grow with seq; the flash kernel keeps them on the
    MXU).  Not measured on the current chip."""
    base = dict(vocab_size=32000, hidden_size=1792,
                intermediate_size=7168, num_layers=16, num_heads=14,
                num_kv_heads=14, max_seq_len=8192,
                remat_policy="full", fused_ce=True)
    return [
        ("0.9B-seq8k", tfm.TransformerConfig(**base), 2, 8192),
        ("0.9B-seq8k-b1", tfm.TransformerConfig(**base), 1, 8192),
    ]


def _large_model_ladder(tfm):
    """Largest-model row.  fp32 master weights + bf16 AdamW moments
    are 8 B/param at rest, so one v5e chip tops out near 1 B params;
    larger shapes belong to a 2+ chip fsdp mesh (the same program
    shards them there).  Not measured on the current chip."""
    return [
        ("1.0B", tfm.TransformerConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=7168,
            num_layers=16, num_heads=16, num_kv_heads=16,
            max_seq_len=2048, remat_policy="full", fused_ce=True),
         6, 2048),
    ]


def _run_ladder(ladder, steps, devices):
    """First config that fits wins: the ladder walks down on HBM
    exhaustion and on nothing else."""
    for name, config, batch, seq in ladder:
        try:
            mfu, tok_per_sec, final_loss = _run_once(
                config, batch, seq, steps, devices)
            return (name, config, batch, seq, mfu, tok_per_sec,
                    final_loss)
        except Exception as e:  # noqa: BLE001 — re-raised unless OOM
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            print(f"# {name} does not fit in HBM ({str(e)[:200]}); "
                  "trying the next config", file=sys.stderr)
    return None


def _row_json(tfm, devices, result):
    name, config, batch, seq, mfu, tok_per_sec, final_loss = result
    return {
        "model": name,
        "mfu": mfu if mfu is None else round(mfu, 4),
        "tokens_per_sec_per_chip": round(tok_per_sec / len(devices), 1),
        "model_params": tfm.num_params(config),
        "seq_len": seq,
        "batch": batch,
        "final_loss": round(final_loss, 4),
    }


def main():
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="run the same code path at tiny shapes on "
                         "whatever backend there is; prints no metric")
    args = ap.parse_args()

    import jax

    from ray_tpu.models import transformer as tfm
    from ray_tpu.util import compile_cache

    compile_cache.enable()
    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"

    if args.rehearse:
        result = _run_ladder(
            [("tiny", tfm.TransformerConfig.tiny(), 4, 64)], 3, devices)
        print(json.dumps({
            "rehearsal": True, "device": devices[0].device_kind,
            "steps": 3, "final_loss": round(result[6], 4)}))
        return 0
    if not on_tpu:
        print(f"bench.py measures a TPU; found {devices[0].platform!r}. "
              "Use --rehearse to exercise the code path off-chip.",
              file=sys.stderr)
        return 2

    headline_ladder = _tpu_config_ladder(tfm)
    extra_ladders = [_long_context_ladder(tfm), _large_model_ladder(tfm)]
    steps = 20
    result = _run_ladder(headline_ladder, steps, devices)
    if result is None:
        print(json.dumps({"metric": "train_mfu", "value": 0.0,
                          "unit": "MFU", "vs_baseline": 0.0,
                          "error": "all configs OOMed"}))
        return 1
    rows = []
    for ladder in extra_ladders:
        try:
            extra = _run_ladder(ladder, steps, devices)
        except Exception:  # noqa: BLE001 — extras must never cost the
            # already-measured headline its JSON line.
            import traceback

            traceback.print_exc(file=sys.stderr)
            extra = None
        if extra is not None:
            rows.append(_row_json(tfm, devices, extra))

    mfu = result[4]
    head = _row_json(tfm, devices, result)
    print(json.dumps({
        "metric": "train_mfu",
        "value": round(mfu, 4),
        "unit": "MFU",
        "vs_baseline": round(mfu / 0.40, 4),
        **{k: v for k, v in head.items() if k != "mfu"},
        "device": devices[0].device_kind,
        "n_devices": len(devices),
        # Long-context + largest-model rows: the headline stays the
        # cross-round-comparable 2048-seq config.
        "extra_rows": rows,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
