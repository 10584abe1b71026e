"""Continuous control from state (paper §3.1 / Fig 4): SAC on Pendulum with
the async runner + host replay — entropy auto-tuning, twin critics, no state-
value function, and TIME-LIMIT BOOTSTRAPPING via terminal_obs (the paper's
footnote-3 fix, reproduced exactly).

  PYTHONPATH=src python examples/mujoco_style_sac.py --iters 150
"""
import argparse

import numpy as np
import jax

from repro.envs import make_env
from repro.agents import make_sac_agent
from repro.algos import SAC
from repro.models.rl_models import make_sac_actor, make_q_critic
from repro.samplers import SerialSampler
from repro.runners import AsyncRunner
from repro.replay.host import TransitionSamples, UniformReplayBuffer
from repro.train.optim import adam
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=150)
    ap.add_argument("--replay-ratio", type=float, default=8.0)
    args = ap.parse_args()

    env = make_env("pendulum")
    actor = make_sac_actor(3, 1, hidden=(64, 64))
    critic = make_q_critic(3, 1, hidden=(64, 64))
    agent = make_sac_agent(actor, 1)
    algo = SAC(actor.apply, critic.apply, adam(1e-3), adam(1e-3), act_dim=1)

    sampler = SerialSampler(env, agent, n_envs=8, horizon=32)
    example = TransitionSamples(
        observation=np.zeros(3, np.float32), action=np.zeros(1, np.float32),
        reward=np.float32(0), done=False, timeout=False)
    # store_next_obs=True: keeps the pre-reset obs so timeout bootstrapping
    # uses the true terminal state (footnote 3)
    buffer = UniformReplayBuffer(example, T_size=8192, B=8, n_step=1,
                                 store_next_obs=True)
    runner = AsyncRunner(sampler, algo, buffer, batch_size=128,
                         replay_ratio=args.replay_ratio, min_replay=1024,
                         n_iterations=args.iters, log_interval=15)
    k = jax.random.PRNGKey(0)
    params = {"actor": actor.init(k), "critic": critic.init(k)}
    runner.run(k, params=params)


if __name__ == "__main__":
    main()
