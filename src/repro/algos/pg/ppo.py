"""PPO (paper §1.1): clipped-surrogate policy optimization with minibatch
epochs.  The whole multi-epoch update compiles to one program (scan over
shuffled minibatches) — the paper's inner optimization loop, TPU-fused.

Also the ``train_step`` the multi-pod dry-run lowers for LM policies: tokens
(B, T) sharded over ('pod','data'), model TP over 'model', GAE via
associative scan, microbatch gradient accumulation for memory.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ...core.algorithm import TrainState, OptInfo
from ...core.batch_spec import BatchSpec
from ...train.optim import Optimizer, compress_metrics
from .gae import gae_scan, gae_associative

F32 = jnp.float32


class PPO:
    batch_spec = BatchSpec("rollout", ("observation", "prev_action",
                                       "prev_reward", "action", "reward",
                                       "done", "value", "logp_old",
                                       "bootstrap_value"))

    def __init__(self, apply_fn: Callable, optimizer: Optimizer, *,
                 distribution, gamma=0.99, gae_lambda=0.95,
                 clip_eps=0.2, value_coeff=0.5, entropy_coeff=0.01,
                 epochs=4, minibatches=4, normalize_advantage=True,
                 value_clip: Optional[float] = None, associative_gae=False):
        self.apply = apply_fn
        self.opt = optimizer
        self.dist = distribution
        self.gamma, self.lam = gamma, gae_lambda
        self.clip_eps = clip_eps
        self.vc, self.ec = value_coeff, entropy_coeff
        self.epochs, self.minibatches = epochs, minibatches
        self.norm_adv = normalize_advantage
        self.value_clip = value_clip
        self.gae = gae_associative if associative_gae else gae_scan

    def init_train_state(self, rng, params) -> TrainState:
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=self.opt.init(params), extra=None)

    # -- advantage computation on the full (T, B) batch ---------------------
    def compute_advantages(self, batch):
        adv, ret = self.gae(batch["reward"], batch["value"],
                            batch["bootstrap_value"], batch["done"],
                            gamma=self.gamma, lam=self.lam)
        return adv, ret

    def loss(self, params, mb):
        logits, value = self.apply(params, mb["observation"],
                                   mb.get("prev_action"), mb.get("prev_reward"))
        logp = self.dist.log_likelihood(mb["action"], logits)
        ratio = jnp.exp(logp - mb["logp_old"])
        adv = mb["advantage"]
        if self.norm_adv:
            adv = (adv - adv.mean()) / (adv.std() + 1e-8)
        surr1 = ratio * adv
        surr2 = jnp.clip(ratio, 1 - self.clip_eps, 1 + self.clip_eps) * adv
        pi_loss = -jnp.mean(jnp.minimum(surr1, surr2))
        if self.value_clip is not None:
            v_old = mb["value"]
            v_clip = v_old + jnp.clip(value - v_old, -self.value_clip, self.value_clip)
            v_loss = 0.5 * jnp.mean(jnp.maximum(jnp.square(value - mb["return_"]),
                                                jnp.square(v_clip - mb["return_"])))
        else:
            v_loss = 0.5 * jnp.mean(jnp.square(value - mb["return_"]))
        ent = jnp.mean(self.dist.entropy(logits))
        total = pi_loss + self.vc * v_loss - self.ec * ent
        clipfrac = jnp.mean((jnp.abs(ratio - 1.0) > self.clip_eps).astype(F32))
        return total, {"pi_loss": pi_loss, "v_loss": v_loss, "entropy": ent,
                       "clipfrac": clipfrac,
                       "approx_kl": jnp.mean(mb["logp_old"] - logp)}

    def update(self, train_state: TrainState, batch, rng):
        """batch: time-major (T, B) with observation/action/reward/done/value/
        logp_old/bootstrap_value.  Runs epochs x minibatches gradient steps."""
        adv, ret = self.compute_advantages(batch)
        T, B = batch["reward"].shape
        flat = {
            "observation": _flatten_tb(batch["observation"]),
            "action": _flatten_tb(batch["action"]),
            "logp_old": batch["logp_old"].reshape(T * B),
            "advantage": adv.reshape(T * B),
            "return_": ret.reshape(T * B),
            "value": batch["value"].reshape(T * B),
        }
        if "prev_action" in batch:
            flat["prev_action"] = _flatten_tb(batch["prev_action"])
            flat["prev_reward"] = batch["prev_reward"].reshape(T * B)
        n = T * B
        mb_size = n // self.minibatches

        def epoch_body(carry, ep_rng):
            params, opt_state = carry
            perm = jax.random.permutation(ep_rng, n)

            def mb_body(carry, i):
                params, opt_state = carry
                idx = jax.lax.dynamic_slice_in_dim(perm, i * mb_size, mb_size)
                mb = jax.tree_util.tree_map(lambda x: x[idx], flat)
                (loss, aux), grads = jax.value_and_grad(self.loss, has_aux=True)(
                    params, mb)
                params, opt_state, gnorm = self.opt.update(grads, opt_state, params)
                return (params, opt_state), (loss, gnorm, aux)

            carry, logs = jax.lax.scan(mb_body, (params, opt_state),
                                       jnp.arange(self.minibatches))
            return carry, logs

        rngs = jax.random.split(rng, self.epochs)
        (params, opt_state), logs = jax.lax.scan(
            epoch_body, (train_state.params, train_state.opt_state), rngs)
        loss, gnorm, aux = logs
        ts = TrainState(step=train_state.step + 1, params=params,
                        opt_state=opt_state, extra=None)
        info = OptInfo(loss=loss.mean(), grad_norm=gnorm.mean(),
                       extra=jax.tree_util.tree_map(jnp.mean, aux))
        return ts, info


def _flatten_tb(x):
    return jax.tree_util.tree_map(
        lambda l: l.reshape((l.shape[0] * l.shape[1],) + l.shape[2:]), x)


# ---------------------------------------------------------------------------
# LM-scale PPO train_step (the dry-run's train_4k target)
# ---------------------------------------------------------------------------

def make_lm_ppo_train_step(cfg, optimizer: Optimizer, *,
                           clip_eps=0.2, value_coeff=0.5, entropy_coeff=0.01,
                           n_microbatches: int = 1, aux_coeff: float = 0.01,
                           img_len: int = 0, enc_len: int = 0,
                           unroll_micro: bool = False, param_pspecs=None):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state, metrics).

    batch (token MDP trajectories, batch-major for sharding over ('pod','data')):
      tokens (B, T) int32        observations = prev tokens
      actions (B, T) int32       sampled next tokens
      logp_old, advantage, return_ (B, T) f32
      [+ img_embed (B, I, D) for vlm; enc_frames (B, S, D) for encdec]

    Microbatch gradient accumulation (scan) bounds activation memory; grads
    accumulate in fp32 with the same sharding as params.
    """
    from ...models import backbones as bb
    from ...models import sharding as shd

    def maybe_cast(params):
        """cfg.cast_weights_bf16 (§Perf): cast weight matrices shard-local
        BEFORE the FSDP all-gather so the gather (and the grad
        reduce-scatter, via the transpose) moves bf16 — half the wire bytes.
        The sharding constraint pins the cast output to the params' own
        (FSDP x TP) layout so XLA cannot gather-then-cast."""
        if not cfg.cast_weights_bf16:
            return params

        def c(x, spec=None):
            if x.ndim >= 2 and x.dtype == jnp.float32:
                y = x.astype(jnp.bfloat16)
                return shd.constrain(y, spec) if spec is not None else y
            return x

        if param_pspecs is not None:
            return jax.tree_util.tree_map(c, params, param_pspecs)
        return jax.tree_util.tree_map(c, params)

    def loss_fn(params, mb):
        kw = {}
        if img_len:
            kw["img"] = mb["img_embed"]
        if enc_len:
            kw["enc_frames"] = mb["enc_frames"]
        hidden, aux = bb.forward_train(params, mb["tokens"], cfg, **kw)
        logits = bb.lm_logits(params, hidden, cfg)
        value = bb.value_out(params, hidden)
        logits = logits.astype(F32)
        logp_all = jax.nn.log_softmax(logits, axis=-1)
        logp = jnp.take_along_axis(logp_all, mb["actions"][..., None], axis=-1)[..., 0]
        ratio = jnp.exp(logp - mb["logp_old"])
        adv = mb["advantage"]
        surr = jnp.minimum(ratio * adv,
                           jnp.clip(ratio, 1 - clip_eps, 1 + clip_eps) * adv)
        pi_loss = -jnp.mean(surr)
        v_loss = 0.5 * jnp.mean(jnp.square(value - mb["return_"]))
        ent = -jnp.mean(jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1))
        total = pi_loss + value_coeff * v_loss - entropy_coeff * ent + aux_coeff * aux
        return total, {"pi_loss": pi_loss, "v_loss": v_loss, "entropy": ent}

    @jax.named_scope("ppo_update")   # the update's device ops carry it
    def train_step(params, opt_state, batch):
        B = batch["tokens"].shape[0]
        assert B % n_microbatches == 0
        mbs = jax.tree_util.tree_map(
            lambda x: x.reshape((n_microbatches, B // n_microbatches) + x.shape[1:]),
            batch)
        fwd_params = maybe_cast(params)

        def constrain_grads(g):
            """Pin grads/accumulator to the params' (FSDP x TP) layout.
            Without this the partitioner REPLICATES the accumulator and
            every microbatch all-gathers full f32 weight-shaped gradients
            (§Perf cell B: the dominant collective at baseline)."""
            if param_pspecs is None:
                return g
            return jax.tree_util.tree_map(shd.constrain, g, param_pspecs)

        def mb_body(acc, mb):
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                fwd_params, mb)
            grads = constrain_grads(grads)
            acc_g, acc_l = acc
            acc_g = constrain_grads(jax.tree_util.tree_map(
                lambda a, g: a + g.astype(F32) / n_microbatches, acc_g, grads))
            return (acc_g, acc_l + loss / n_microbatches), aux

        from ...models.layers import scan_or_unroll
        zero_g = constrain_grads(jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, F32), params))
        (grads, loss), auxes = scan_or_unroll(
            mb_body, (zero_g, jnp.zeros((), F32)), mbs, unroll_micro)
        params2, opt_state2, gnorm = optimizer.update(grads, opt_state, params)
        metrics = {"loss": loss, "grad_norm": gnorm,
                   **jax.tree_util.tree_map(jnp.mean, auxes)}
        metrics.update(compress_metrics(opt_state2))
        return params2, opt_state2, metrics

    return train_step
