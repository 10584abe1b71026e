"""Operations and bytes that the work needs, computed from shapes alone.

These count what the algorithm requires, not what one implementation
happens to move, so a kernel's roofline share is comparable across
implementations.  A multiply-add counts as two operations.
"""
from __future__ import annotations


def mamba2_dims(model: dict) -> dict:
    D = model["d_model"]
    inner = model["ssm_expand"] * D
    P = model["ssm_headdim"]
    return {"D": D, "L": model["n_layers"], "H": inner // P, "P": P,
            "G": model["ssm_n_groups"], "N": model["d_state"],
            "K": model["conv_kernel"]}


def mamba2_matmul_params(model: dict, padded_vocab: int) -> int:
    """Parameters that take part in a matrix product for each token: the
    layers' input and output projections, the LM head and the value head.
    The embedding is a lookup and is not counted."""
    d = mamba2_dims(model)
    D, H, P, G, N = d["D"], d["H"], d["P"], d["G"], d["N"]
    per_layer = D * (2 * H * P + 2 * G * N + H) + H * P * D
    return d["L"] * per_layer + D * padded_vocab + D


def mamba2_token_flops(model: dict, padded_vocab: int) -> float:
    """Forward operations per token: 2 per matmul parameter, plus the
    depthwise convolution and the state recurrence of every layer (the
    recurrence's minimal work: decay and input outer product into the
    (H, P, N) state, and the read-out, 5 H P N)."""
    d = mamba2_dims(model)
    conv = 2 * d["K"] * (d["H"] * d["P"] + 2 * d["G"] * d["N"])
    scan = 5 * d["H"] * d["P"] * d["N"]
    return 2.0 * mamba2_matmul_params(model, padded_vocab) \
        + d["L"] * (conv + scan)


def lm_ppo_step_flops(model: dict, padded_vocab: int, batch: int,
                      horizon: int) -> float:
    """One LM-PPO step: the rollout's forward over batch x horizon tokens,
    then the update's forward and backward over the same tokens (3x the
    forward).  Recomputation under remat is not counted."""
    return 4.0 * mamba2_token_flops(model, padded_vocab) * batch * horizon


def ssd_op(B: int, T: int, H: int, P: int, G: int, N: int, *,
           act_bytes: int = 2, f32_bytes: int = 4):
    """(operations, bytes) of one SSD forward over (B, T): the recurrence's
    minimal work, and its inputs read and outputs written once: x and y
    (B, T, H, P) and B, C (B, T, G, N) in the activations' type, dt
    (B, T, H), A (H,) and the final state (B, H, P, N) in float32."""
    flops = 5.0 * B * T * H * P * N
    nbytes = (act_bytes * (2 * B * T * H * P + 2 * B * T * G * N)
              + f32_bytes * (B * T * H + H + B * H * P * N))
    return flops, nbytes
