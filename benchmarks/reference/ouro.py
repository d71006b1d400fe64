"""Plain reference for the Ouro architecture (ByteDance's LoopLM release,
``model_type: "ouro"``): one stack of decoder blocks run ``total_ut_steps``
times a token over ONE set of weights, an exit gate choosing which pass's
output feeds the untied head.

With ``E`` the embedding, ``B_1 .. B_L`` the blocks, ``n_f`` the final
RMSNorm, ``T = total_ut_steps`` and ``theta = early_exit_threshold``::

    block B_l (sandwich norm, four RMSNorm gains a layer):
        h = x + n2_l(Attn_l(n1_l(x)))
        y = h + n4_l(MLP_l(n3_l(h)))
      Attn_l: multi-head causal softmax attention over all earlier positions
              of the SAME pass, rotary embedding (half-split pairing, the
              whole head, theta_rope), scale head_dim^-1/2, no bias, no window
      MLP_l(u) = W_down(silu(W_gate u) * W_up u)
    recurrence:  x^0 = E[tokens];  x^t = n_f(B_L(.. B_1(x^(t-1))))  t = 1..T
        (the same blocks and the same n_f every pass: the normed output of a
        pass is the next pass's input)
    exit gate:   lambda_t = sigmoid(w_g . x^t + b_g)       one Linear(d, 1)
        p_t = lambda_t prod_(j<t) (1 - lambda_j)  for t < T,  p_T the remainder
        a token leaves at the first t whose cumulative p reaches theta, else
        at T;  logits = W_head x^exit  (x^exit is already normed)

Pass ``t`` of layer ``l`` attends to the keys and values that pass ``t`` of
layer ``l`` made for the earlier tokens: a serving cache holds ``T x L``
layers of K/V, index ``(t - 1) L + l``. Here there is no cache: every pass
is a full forward over the whole sequence. All ``T`` passes are always
computed; the exit only chooses a row. At the published ``theta = 1`` every
token leaves at pass ``T``.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching tricks, and no code of ``deepspeed_tpu``. It reads the weight tree
the benchmark made from the seed (``benchmarks/weights.py``) and shares the
linear layer with its rounding control (``quant``), the norm, rotary
attention and the feed-forward with ``reference/mistral.py``.

Assumed, where the catalog's ``config`` is silent (it carries no modelling
code; the configuration file lists the same under ``assumed``):

* the block's sandwich wiring above, the gains named, in the checkpoint,
  ``input_layernorm`` (n1), ``input_layernorm_2`` (n2),
  ``post_attention_layernorm`` (n3), ``post_attention_layernorm_2`` (n4);
* the final norm after EVERY pass, and not again before the head;
* K/V of its own for every pass (no sharing, no averaged or last-pass cache);
* the exit rule above: cumulative probability against the threshold, the
  last pass taking the remainder, every pass computed;
* rotary pairing half-split over the whole head, as the Llama family;
* the gate computed in float32 (never rounded by ``quant``: it is no part of
  the served precision).

What ``benchmarks/weights.py`` gives the new leaves (by its rules on a
leaf's name and shape): the four ``*_norm_w`` gains a layer and
``final_norm_w`` near one, ``exit_gate_w`` [d, 1] normal with ``d^-0.5`` (a
gate logit of about unit variance), ``b_exit_gate`` zero.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from benchmarks.reference import mistral as base

F32 = jnp.float32


def block(x, layers, li, cfg, quant):
    """Layer ``li`` of the stacked tree (index taken inside the program)."""
    lw = jax.tree_util.tree_map(lambda a: a[li], layers)
    eps = cfg["rms_norm_eps"]
    a = base.attention(base.rms_norm(x, lw["attn_norm_w"], eps), lw, cfg,
                       quant)
    h = x + base.rms_norm(a, lw["attn_post_norm_w"], eps)
    m = base.dense_mlp(base.rms_norm(h, lw["mlp_norm_w"], eps), lw, cfg,
                       quant)
    return h + base.rms_norm(m, lw["mlp_post_norm_w"], eps)


@functools.lru_cache(maxsize=None)
def _jitted_block(static_cfg: Tuple, quant):
    cfg = dict(static_cfg)

    def run(x, layers, li):
        with jax.default_matmul_precision("highest"):
            return block(x, layers, li, cfg, quant)

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _jitted_close(eps: float):
    """What closes a pass: the final norm, and the gate's lambda on it."""

    def run(x, gain, w_g, b_g):
        with jax.default_matmul_precision("highest"):
            x = base.rms_norm(x, gain, eps)
            lam = jax.nn.sigmoid(x @ w_g.astype(F32)[:, 0]
                                 + b_g.astype(F32)[0])
            return x, lam

    return jax.jit(run)


def exit_pass(lams, threshold: float):
    """lams [T, ...] -> for each token the index (from 0) of the pass it
    leaves at: the first whose cumulative exit probability reaches
    ``threshold``, else the last."""
    T = lams.shape[0]
    if T == 1:
        return jnp.zeros(lams.shape[1:], jnp.int32)
    stayed = jnp.cumprod(1.0 - lams, 0)                    # prod_(j<=t)
    before = jnp.concatenate([jnp.ones_like(stayed[:1]), stayed[:-1]])
    p = lams * before
    p = p.at[-1].set(1.0 - jnp.sum(p[:-1], 0))             # the remainder
    reached = jnp.cumsum(p, 0)[:-1] >= threshold           # passes 1..T-1
    first = jnp.argmax(reached, 0)
    return jnp.where(jnp.any(reached, 0), first, T - 1)


def passes(weights, tokens, cfg, n_layers: int, quant=None):
    """tokens [b, s] -> (normed hidden states of every pass [T, b, s, d],
    the gate's lambda [T, b, s]) in float32."""
    x = weights["tok_embed"][tokens].astype(F32)
    run = _jitted_block(base._static_cfg(cfg), quant)
    close = _jitted_close(cfg["rms_norm_eps"])
    T = int(cfg["total_ut_steps"])
    # one pass has no gate to its name: everything leaves at it
    w_g = weights.get("exit_gate_w", jnp.zeros((x.shape[-1], 1), F32))
    b_g = weights.get("b_exit_gate", jnp.zeros((1,), F32))
    xs, lams = [], []
    for _ in range(T):
        for li in range(n_layers):
            x = run(x, weights["layers"], li)
        x, lam = close(x, weights["final_norm_w"], w_g, b_g)
        xs.append(x)
        lams.append(lam)
    return jnp.stack(xs), jnp.stack(lams)


def hidden(weights, tokens, cfg, n_layers: int, quant=None):
    """tokens [b, s] -> the hidden states that feed the head [b, s, d]:
    each token's exit pass's, already normed."""
    xs, lams = passes(weights, tokens, cfg, n_layers, quant)
    at = exit_pass(lams, float(cfg["early_exit_threshold"]))
    return jnp.take_along_axis(xs, at[None, ..., None], 0)[0]


@functools.lru_cache(maxsize=None)
def _jitted_head(quant):
    def run(x, head):
        with jax.default_matmul_precision("highest"):
            return base.linear(x, head, quant)

    return jax.jit(run)


def logits_at(weights, tokens, rows, cols, cfg, n_layers: int, quant=None):
    """Logits [n, vocab] of the full forward over ``tokens`` [b, s] at the
    positions (rows[i], cols[i])."""
    x = hidden(weights, tokens, cfg, n_layers, quant)[rows, cols]
    return _jitted_head(quant)(x, weights["lm_head"])
