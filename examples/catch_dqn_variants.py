"""Discrete control from vision (paper §3.2 / Fig 6): DQN and its variants
(Double, Dueling, Categorical/C51, prioritized, n-step) on Catch, using the
fused device-replay runner — collect+insert+sample+update in ONE compiled
program per iteration.

  PYTHONPATH=src python examples/catch_dqn_variants.py --variant rainbow
"""
import argparse

import jax
import jax.numpy as jnp

from repro.envs import make_env
from repro.agents import make_dqn_agent
from repro.algos import DQN
from repro.models.rl_models import make_q_conv
from repro.samplers import SerialSampler
from repro.runners import OffPolicyRunner
from repro.train.optim import adam
from repro.utils.compile_cache import enable_compile_cache

VARIANTS = {
    "dqn": dict(double=False, dueling=False, n_atoms=0, prioritized=False),
    "double": dict(double=True, dueling=False, n_atoms=0, prioritized=False),
    "dueling": dict(double=True, dueling=True, n_atoms=0, prioritized=True),
    "c51": dict(double=False, dueling=False, n_atoms=21, prioritized=False),
    # rainbow-minus-noisy = double + dueling + C51 + prioritized (paper §1.1)
    "rainbow": dict(double=True, dueling=True, n_atoms=21, prioritized=True),
}


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", choices=sorted(VARIANTS), default="rainbow")
    ap.add_argument("--iters", type=int, default=150)
    args = ap.parse_args()
    v = VARIANTS[args.variant]

    env = make_env("catch")
    model = make_q_conv(1, 3, img_hw=(10, 5), channels=(16, 32),
                        kernels=(3, 3), strides=(1, 1), d_out=128,
                        dueling=v["dueling"], n_atoms=v["n_atoms"])
    agent = make_dqn_agent(model, 3, n_atoms=v["n_atoms"], v_min=-1, v_max=1)
    algo = DQN(model.apply, adam(5e-4), gamma=0.99, double=v["double"],
               n_atoms=v["n_atoms"], v_min=-1, v_max=1,
               target_update_interval=100)
    sampler = SerialSampler(env, agent, n_envs=16, horizon=16)
    runner = OffPolicyRunner(sampler, algo, replay_capacity=8192,
                             batch_size=64, n_iterations=args.iters,
                             updates_per_collect=2, min_replay=512,
                             prioritized=v["prioritized"], log_interval=25,
                             agent_state_kwargs={"epsilon": 0.2})
    ts, ss, _ = runner.run(jax.random.PRNGKey(0))
    # greedy evaluation
    ss = sampler.reset_stats(ss)._replace(agent_state={"epsilon": jnp.zeros(16)})
    for _ in range(4):
        ss, _ = jax.jit(sampler.collect)(ts.params, ss)
    print(f"[{args.variant}] greedy eval:",
          {k: float(x) for k, x in sampler.traj_stats(ss).items()})


if __name__ == "__main__":
    main()
