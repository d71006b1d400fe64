"""Token sampling shared by the dense-cache engine, the ragged engine and
the hybrid engine's rollout."""

import jax
import jax.numpy as jnp


def sample(logits, rng, temperature: float, top_k: int, top_p: float):
    """Greedy when temperature==0, else temperature/top-k/top-p sampling."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / jnp.maximum(temperature, 1e-6)
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def decide_masked(logits, masked, n: int, mask_id: int):
    """A denoise pass of block diffusion (SDAR's ``low_confidence_static``
    remasking): logits [..., B, vocab] at a block's positions, ``masked``
    [..., B] bool (positions not decided yet). Every masked position
    predicts its own token, ``x0 = argmax`` of its own logits (no shift by
    one), with confidence the softmax probability of ``x0`` in float32; the
    ``min(n, masked)`` masked positions of highest confidence are decided,
    ties to the lower position. No position decides ``mask_id`` itself (it
    would stay masked, and decide it again, for ever): that logit is left
    out of the argmax and of the softmax. Returns int32 [..., B]: ``x0``
    where decided in this pass, ``-1`` elsewhere."""
    l32 = logits.astype(jnp.float32)
    l32 = jnp.where(jnp.arange(l32.shape[-1]) == mask_id, -jnp.inf, l32)
    top = jnp.max(l32, axis=-1)
    x0 = jnp.argmax(l32, axis=-1).astype(jnp.int32)
    conf = 1.0 / jnp.sum(jnp.exp(l32 - top[..., None]), axis=-1)
    score = jnp.where(masked, conf, -1.0)          # a confidence is > 0
    B = score.shape[-1]
    # rank of each position by (score descending, position ascending)
    better = (score[..., None, :] > score[..., :, None]) | (
        (score[..., None, :] == score[..., :, None])
        & (jnp.arange(B)[None, :] < jnp.arange(B)[:, None]))
    rank = jnp.sum(better, axis=-1)
    return jnp.where(masked & (rank < n), x0, -1)
