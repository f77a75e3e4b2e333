"""Drive DreamerV3 end-to-end through the public API: recurrent acting,
sequence replay, world-model + actor-critic updates, checkpoint
roundtrip, evaluation."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"  # these drives run on the host CPU
os.environ.setdefault("RAY_TPU_CHIPS", "none")

import jax  # noqa: E402

import numpy as np  # noqa: E402


def main():
    import tempfile

    from ray_tpu.rl.algorithms import DreamerV3Config

    cfg = DreamerV3Config().environment("CartPole-v1")
    cfg.deter_dim = 32; cfg.stoch_vars = 4; cfg.stoch_classes = 4
    cfg.units = 32; cfg.mlp_layers = 1
    cfg.batch_size_B = 4; cfg.batch_length_T = 8; cfg.horizon = 5
    cfg.rollout_fragment_length = 32
    cfg.num_steps_sampled_before_learning_starts = 64
    cfg.training_ratio = 8.0
    algo = cfg.build()
    t0 = time.time()
    for i in range(5):
        res = algo.train()
    assert np.isfinite(res["wm_loss"]), res
    print(f"[1] 5 iters in {time.time() - t0:.1f}s  "
          f"wm_loss={res['wm_loss']:.2f} entropy={res['entropy']:.2f} "
          f"return={res.get('episode_return_mean'):.1f}")

    with tempfile.TemporaryDirectory() as d:
        algo.save_checkpoint(d)
        it = algo.iteration
        algo.load_checkpoint(d)
        assert algo.iteration == it
    print("[2] checkpoint save/load roundtrip ok")

    ev = algo.evaluate(num_episodes=2)
    assert ev["evaluation/num_episodes"] == 2
    print(f"[3] eval return={ev['evaluation/episode_return_mean']:.1f}")
    res = algo.train()  # training continues after eval + restore
    assert np.isfinite(res["wm_loss"])
    print("[4] training continues after eval/restore")
    algo.stop()
    print("ALL OK")


if __name__ == "__main__":
    main()
