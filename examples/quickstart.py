"""Quickstart: PPO on CartPole in ~30 lines — the paper's serial-mode
debugging workflow (§2.4: "serial mode will be easiest for debugging").

The runner compiles each log window (collect -> update x log_interval) into
ONE lax.scan program via the scan-fused TrainLoop; pass ``fuse=False`` to
dispatch one program per iteration instead (see docs/architecture.md).

  PYTHONPATH=src python examples/quickstart.py [log_dir]
"""
import sys

import jax

from repro.envs import make_env
from repro.agents import make_categorical_pg_agent
from repro.algos import PPO
from repro.core.distributions import Categorical
from repro.models.rl_models import make_pg_mlp
from repro.samplers import EvalSampler, SerialSampler
from repro.runners import OnPolicyRunner
from repro.train.optim import adam
from repro.utils.logger import Logger
from repro.utils.compile_cache import enable_compile_cache


def main(log_dir="logs/quickstart"):
    enable_compile_cache()
    env = make_env("cartpole")
    model = make_pg_mlp(obs_dim=4, n_actions=2)
    agent = make_categorical_pg_agent(model)
    algo = PPO(model.apply, adam(7e-4, grad_clip=0.5),
               distribution=Categorical(2), epochs=4, minibatches=4)
    sampler = SerialSampler(env, agent, n_envs=16, horizon=64)
    # offline evaluation (paper §2.1): dedicated envs, greedy agent,
    # reported as eval_* in every log row
    evaluator = EvalSampler(env, agent, n_envs=8, max_steps=2000,
                            max_episodes=8)
    # sentinels ride the fused scan (telemetry/sentinels.py): grad/param/
    # update norms, non-finite counts, env steps land as sent_* columns in
    # progress.csv / progress.jsonl alongside the training stats
    runner = OnPolicyRunner(sampler, algo, n_iterations=50, log_interval=10,
                            eval_sampler=evaluator, sentinels=True,
                            logger=Logger(log_dir))
    train_state, sampler_state, _ = runner.run(jax.random.PRNGKey(0))
    print("final stats:", {k: float(v) for k, v in
                           sampler.traj_stats(sampler_state).items()})


if __name__ == "__main__":
    main(*sys.argv[1:])
