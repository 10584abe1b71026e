"""lm_ppo_tokens_per_s: batch x horizon tokens of every PPO step completed
in the window (rollout and update both), over the window's seconds."""


def read(run, trace):
    return run.record["tokens"] / run.record["window_s"]
