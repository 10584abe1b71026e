"""R2D1 (paper §3.2, Figs 7-8): recurrent agent + ASYNC runner + ALTERNATING
sampler + prioritized SEQUENCE replay with periodic recurrent-state storage
and burn-in — the paper's headline pipeline, end to end.

  PYTHONPATH=src python examples/r2d1_recurrent.py --iters 120
"""
import argparse

import numpy as np
import jax
import jax.numpy as jnp

from repro.envs import make_env
from repro.agents import make_r2d1_agent
from repro.algos import R2D1
from repro.models.rl_models import make_recurrent_q
from repro.samplers import AlternatingSampler
from repro.runners import AsyncR2D1Runner
from repro.replay.host import SequenceSamples, SequenceReplayBuffer
from repro.train.optim import adam
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=120)
    ap.add_argument("--replay-ratio", type=float, default=2.0)
    args = ap.parse_args()

    env = make_env("catch")
    d_lstm = 64
    model = make_recurrent_q(1, 3, conv=True, img_hw=(10, 5), d_lstm=d_lstm,
                             channels=(16, 32), kernels=(3, 3),
                             strides=(1, 1), d_conv_out=128, dueling=True)
    agent = make_r2d1_agent(model, 3)
    algo = R2D1(model.apply, adam(5e-4), burn_in=4, n_step=2, gamma=0.99,
                target_update_interval=200)
    # horizon == state_interval: recurrent state stored once per block
    sampler = AlternatingSampler(env, agent, n_envs=16, horizon=8)
    obs0 = np.zeros((10, 5, 1), np.float32)
    st0 = (np.zeros((d_lstm,), np.float32), np.zeros((d_lstm,), np.float32))
    example = SequenceSamples(observation=obs0, prev_action=np.int32(0),
                              prev_reward=np.float32(0), action=np.int32(0),
                              reward=np.float32(0), done=False,
                              init_state=st0)
    buffer = SequenceReplayBuffer(example, T_size=2048, B=16, seq_len=16,
                                  burn_in=4, state_interval=8)
    runner = AsyncR2D1Runner(sampler, algo, buffer, batch_size=32,
                             replay_ratio=args.replay_ratio, min_replay=512,
                             n_iterations=args.iters, log_interval=20,
                             agent_state_kwargs={"epsilon": 0.2})
    ts, ss, _ = runner.run(jax.random.PRNGKey(0))
    print("done; final loss logged above")


if __name__ == "__main__":
    main()
