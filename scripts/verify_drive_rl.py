"""End-to-end drive of the RL layer through the real runtime.

Covers: PPO local mode learning on CartPole, remote env runners + remote
learners (full multi-process path), runner kill + restart, checkpoint
save/restore.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("RAY_TPU_CHIPS", "none")

import jax  # noqa: E402

import time  # noqa: E402

import numpy as np  # noqa: E402

import ray_tpu  # noqa: E402
from ray_tpu.rl.algorithms import PPOConfig  # noqa: E402


def main():
    t0 = time.time()

    # [1] Local-mode PPO learns CartPole.
    config = (PPOConfig()
              .environment("CartPole-v1")
              .env_runners(num_envs_per_env_runner=8)
              .training(train_batch_size=2048, lr=3e-4, minibatch_size=256,
                        num_epochs=6, entropy_coeff=0.01)
              .debugging(seed=0))
    algo = config.build()
    first = algo.step()["episode_return_mean"]
    last = first
    for _ in range(11):
        last = algo.step()["episode_return_mean"]
    assert last > first + 20, (first, last)
    print(f"[1] local PPO learns: {first:.1f} -> {last:.1f} "
          f"({time.time()-t0:.1f}s)")

    # [2] checkpoint roundtrip.
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        algo.save_checkpoint(d)
        algo2 = (PPOConfig().environment("CartPole-v1")
                 .training(train_batch_size=256, minibatch_size=64,
                           num_epochs=1)).build()
        algo2.load_checkpoint(d)
        w1 = algo.learner_group.get_weights()
        w2 = algo2.learner_group.get_weights()
        np.testing.assert_allclose(
            np.asarray(w1["pi"]["layers"][0]["w"]),
            np.asarray(w2["pi"]["layers"][0]["w"]))
        algo2.stop()
    algo.stop()
    print(f"[2] checkpoint roundtrip ok ({time.time()-t0:.1f}s)")

    # [3] Full multi-process path: remote runners + remote learners.
    ray_tpu.init(num_cpus=6)
    config = (PPOConfig()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=2, num_envs_per_env_runner=2)
              .learners(num_learners=2)
              .training(train_batch_size=512, minibatch_size=128,
                        num_epochs=2))
    algo = config.build()
    r = algo.step()
    assert r["num_env_steps_trained"] >= 256, r
    print(f"[3] remote runners+learners step ok ({time.time()-t0:.1f}s)")

    # [4] kill an env runner mid-run; group restarts it.
    ray_tpu.kill(algo.env_runner_group.remote_runners[1])
    r = algo.step()
    assert r["num_env_steps_trained"] >= 256, r
    print(f"[4] runner kill + restart ok ({time.time()-t0:.1f}s)")
    algo.stop()

    # [5] DQN with remote env runners: QNetworkSpec ships to actors,
    # replay + target sync + greedy evaluate() work end to end.
    from ray_tpu.rl.algorithms import DQNConfig
    dqn = (DQNConfig().environment("CartPole-v1")
           .env_runners(num_env_runners=2, num_envs_per_env_runner=2,
                        rollout_fragment_length=64)
           .training(train_batch_size=32, hidden_sizes=(32,),
                     num_steps_sampled_before_learning_starts=100,
                     training_intensity=2.0)
           .debugging(seed=0)).build()
    for _ in range(4):
        r = dqn.step()
    assert r.get("num_grad_steps", 0) > 0, r
    ev = dqn.evaluate(num_episodes=2)
    # A multi-env runner can finish several episodes in one vector step.
    assert ev["evaluation/num_episodes"] >= 2
    dqn.stop()
    print(f"[5] DQN remote runners + evaluate ok ({time.time()-t0:.1f}s)")

    # [6] APPO: async in-flight sampling over remote runners.
    from ray_tpu.rl.algorithms import APPOConfig
    appo = (APPOConfig().environment("CartPole-v1")
            .env_runners(num_env_runners=2, num_envs_per_env_runner=2,
                         rollout_fragment_length=64)
            .training(train_batch_size=128)
            .debugging(seed=0)).build()
    trained = 0
    for _ in range(5):
        trained += appo.step().get("num_env_steps_trained", 0)
    assert trained > 0
    appo.stop()
    print(f"[6] APPO async sampling ok ({time.time()-t0:.1f}s)")

    # [7] SAC smoke on Pendulum (continuous actions, local mode).
    from ray_tpu.rl.algorithms import SACConfig
    sac = (SACConfig().environment("Pendulum-v1")
           .env_runners(num_envs_per_env_runner=2,
                        rollout_fragment_length=64)
           .training(train_batch_size=32, hidden_sizes=(32,),
                     num_steps_sampled_before_learning_starts=64,
                     training_intensity=1.0)
           .debugging(seed=0)).build()
    r = sac.step()
    r = sac.step()
    assert "critic_loss" in r, r
    sac.stop()
    print(f"[7] SAC continuous-control step ok ({time.time()-t0:.1f}s)")

    # [8] BC from offline episodes.
    from ray_tpu.rl.algorithms import BCConfig
    from ray_tpu.rl.episode import SingleAgentEpisode
    rng = np.random.default_rng(0)
    eps = []
    for _ in range(4):
        ep = SingleAgentEpisode()
        obs = rng.normal(size=(11, 4)).astype(np.float32)
        ep.add_reset(obs[0])
        for t in range(10):
            ep.add_step(obs[t + 1], int(obs[t][0] > 0), 1.0,
                        terminated=t == 9)
        eps.append(ep)
    bc = (BCConfig().environment("CartPole-v1")
          .offline_data(input_episodes=eps)
          .training(train_batch_size=32, num_sgd_iter=4)).build()
    r = bc.step()
    assert "bc_logp" in r, r
    bc.stop()
    print(f"[8] BC offline training ok ({time.time()-t0:.1f}s)")

    ray_tpu.shutdown()
    

if __name__ == "__main__":
    main()


def drive_multi_agent():
    """Multi-policy PPO on a 2-agent coordination game: returns climb
    and both policies train."""
    import numpy as np

    from ray_tpu.rl.multi_agent import MultiAgentEnv, MultiAgentPPOConfig

    class TargetMatch(MultiAgentEnv):
        N = 4
        possible_agents = ["a0", "a1"]
        agent_specs = {"a0": (4, 4, True), "a1": (4, 4, True)}

        def __init__(self, seed: int = 0):
            self._rng = np.random.default_rng(seed)
            self._t = 0

        def _obs(self):
            self._targets = {a: int(self._rng.integers(0, self.N))
                             for a in self.possible_agents}
            return {a: np.eye(self.N, dtype=np.float32)[t]
                    for a, t in self._targets.items()}

        def reset(self, *, seed=None):
            self._t = 0
            return self._obs(), {}

        def step(self, action_dict):
            rewards = {a: float(int(action_dict[a]) == self._targets[a])
                       for a in action_dict}
            self._t += 1
            done = self._t >= 6
            obs = {} if done else self._obs()
            flags = {a: done for a in self.possible_agents}
            flags["__all__"] = done
            return obs, rewards, flags, {"__all__": False}, {}

    cfg = MultiAgentPPOConfig().environment(env_fn=TargetMatch)
    cfg.train_batch_size = 256
    cfg.minibatch_size = 128
    cfg.num_epochs = 6
    cfg.lr = 5e-3
    cfg = cfg.multi_agent(
        policies={"p0": None, "p1": None},
        policy_mapping_fn=lambda a: "p0" if a == "a0" else "p1")
    algo = cfg.build()
    try:
        first = algo.train().get("episode_return_mean", 0.0)
        for _ in range(7):
            res = algo.train()
        final = res["episode_return_mean"]
        assert final > 3.0, (first, final)
        print(f"[MA] multi-policy PPO: return {first:.2f} -> {final:.2f} "
              f"(max 6.0), policies trained: "
              f"{sorted({k.split('/')[0] for k in res if '/' in k})}")
    finally:
        algo.stop()


def drive_catalog_lstm():
    """Catalog model_config path (rl/catalog.py) + recurrent module:
    use_lstm PPO beats the 0.5 memoryless ceiling on RecallEnv."""
    from ray_tpu.rl import RecurrentRLModuleSpec
    from ray_tpu.rl.algorithms import PPOConfig
    from ray_tpu.rl.envs import RecallEnv

    cfg = (PPOConfig()
           .environment(env_fn=lambda: RecallEnv(length=4))
           .env_runners(num_envs_per_env_runner=8)
           .rl_module(model_config={"use_lstm": True,
                                    "lstm_cell_size": 32,
                                    "fcnet_hiddens": [32],
                                    "max_seq_len": 8})
           .training(train_batch_size=512, minibatch_size=256,
                     lr=3e-3, num_epochs=6, entropy_coeff=0.01)
           .debugging(seed=0))
    algo = cfg.build()
    try:
        assert isinstance(algo.env_runner_group.spec,
                          RecurrentRLModuleSpec)
        best = 0.0
        for _ in range(20):
            best = max(best, algo.step().get("episode_return_mean", 0.0))
            if best > 0.8:
                break
        assert best > 0.8, best
        print(f"[LSTM] catalog use_lstm PPO: RecallEnv return {best:.2f} "
              "(memoryless ceiling 0.5)")
    finally:
        algo.stop()


drive_multi_agent()
drive_catalog_lstm()
print("RL DRIVE OK")
