"""Drive the LLM inference stack end-to-end: continuous batching,
prefix caching (parity + measured savings), and the serve deployment."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"  # these drives run on the host CPU
os.environ.setdefault("RAY_TPU_CHIPS", "none")

import jax  # noqa: E402

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ray_tpu.models import transformer as tfm  # noqa: E402
from ray_tpu.serve.llm_engine import LLMEngine  # noqa: E402


def main():
    config = tfm.TransformerConfig.tiny(
        num_layers=2, num_heads=4, num_kv_heads=2, hidden_size=32,
        intermediate_size=64, vocab_size=64, max_seq_len=256,
        dtype=jnp.float32, use_flash=False, scan_layers=True)
    params = tfm.init_params(config, jax.random.key(0))
    rng = np.random.default_rng(0)

    # Shared system-prompt style workload: one long prefix, many tails.
    prefix = rng.integers(0, 64, size=96).tolist()
    prompts = [prefix + rng.integers(0, 64, size=8).tolist()
               for _ in range(6)]

    cold = LLMEngine(config, params, page_size=16, num_pages=128,
                     max_batch=2, enable_prefix_caching=False)
    t0 = time.perf_counter()
    expected = [cold.generate([p], max_new_tokens=8)[0] for p in prompts]
    t_cold = time.perf_counter() - t0

    warm = LLMEngine(config, params, page_size=16, num_pages=128,
                     max_batch=2, enable_prefix_caching=True)
    t0 = time.perf_counter()
    got = [warm.generate([p], max_new_tokens=8)[0] for p in prompts]
    t_warm = time.perf_counter() - t0

    assert got == expected, "prefix-cached decode diverged from cold"
    saved = warm.prefix_cache.tokens_saved
    assert saved >= 5 * 96, saved  # requests 2..6 reuse the 96-tok prefix
    print(f"[1] prefix caching: parity OK, {saved} prompt tokens skipped, "
          f"{warm.prefix_cache.hits} hits "
          f"(cold {t_cold:.2f}s vs warm {t_warm:.2f}s)")

    # Continuous batching with mixed hit/miss admission.
    out = warm.generate(prompts[:3] + [rng.integers(0, 64, 16).tolist()],
                        max_new_tokens=4)
    assert all(len(o) == 4 for o in out)
    print("[2] continuous batching with mixed cached/uncached admits OK")

    # MoE decoding: greedy engine output == full forward argmax.
    moe_cfg = tfm.TransformerConfig.tiny(
        num_layers=2, num_heads=4, num_kv_heads=2, hidden_size=32,
        intermediate_size=32, vocab_size=64, max_seq_len=64,
        num_experts=4, num_experts_per_token=2, capacity_factor=8.0,
        dtype=jnp.float32, use_flash=False, scan_layers=True)
    moe_params = tfm.init_params(moe_cfg, jax.random.key(1))
    prompt = rng.integers(0, 64, size=9).tolist()
    seq = list(prompt)
    for _ in range(6):
        logits = tfm.forward(moe_params, jnp.asarray([seq]),
                             config=moe_cfg)
        seq.append(int(np.argmax(np.asarray(logits)[0, len(seq) - 1])))
    eng = LLMEngine(moe_cfg, moe_params, page_size=4, num_pages=64,
                    max_batch=2)
    got = eng.generate([prompt], max_new_tokens=6)[0]
    assert got == seq[len(prompt):], (got, seq[len(prompt):])
    print("[3] MoE decode == MoE forward argmax, token for token")

    # Speculative decoding: exact greedy outputs, fewer device steps.
    # (Exactness holds at fp32; bf16 configs could tie-break argmax
    # differently between the verify and decode programs — still a
    # valid greedy continuation, just not bitwise-identical.)
    rep_prompt = ([5, 9, 2, 14] * 10)[:38]
    plain = LLMEngine(config, params, page_size=16, num_pages=128,
                      max_batch=1)
    t0 = time.perf_counter()
    exp = plain.generate([rep_prompt], max_new_tokens=24)[0]
    t_plain = time.perf_counter() - t0
    spec = LLMEngine(config, params, page_size=16, num_pages=128,
                     max_batch=1, speculative_k=6, speculative_ngram=2)
    t0 = time.perf_counter()
    got = spec.generate([rep_prompt], max_new_tokens=24)[0]
    t_spec = time.perf_counter() - t0
    assert got == exp, "speculative decode diverged from plain greedy"
    rate = spec.spec_accepted / max(1, spec.spec_drafted)
    print(f"[4] speculative decode: parity OK, "
          f"{spec.spec_accepted}/{spec.spec_drafted} drafts accepted "
          f"({rate:.0%}), {spec.spec_steps} verify steps for 24 tokens "
          f"(plain {t_plain:.2f}s vs spec {t_spec:.2f}s)")
    print("ALL OK")


if __name__ == "__main__":
    main()
