"""Mixture-of-Experts: gating + expert-parallel dispatch.

Capability parity with the reference's ``deepspeed/moe/``:
  - ``TopKGate`` (sharded_moe.py:393; top1gating :184, top2gating :282) —
    top-1/top-2 routing with capacity factor, load-balancing aux loss,
    random token priority, min-capacity floor;
  - ``MOELayer`` (sharded_moe.py:425) — einsum dispatch → ``_AllToAll``
    (:95) over the expert-parallel group → local expert FFNs
    (moe/experts.py) → all-to-all back + weighted combine;
  - drop-token capacity semantics.

TPU-native redesign: the dispatch/combine einsums ARE the GShard dense
formulation, which XLA lowers onto the MXU; expert weights are stacked
``[E, ...]`` and sharded over the ``expert`` mesh axis, so GSPMD inserts
the all-to-alls the reference issues by hand through autograd functions.
No per-expert Python loop exists at any point.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class GateConfig:
    """``n_experts`` is the router's width: every expert of the layer. The
    weights' leading axis is the experts this holder HOLDS, ``experts_held``
    (a range ``(first, past)`` of router ids; None = all of them): a layer
    told which experts it holds routes over all ``n_experts`` and computes
    its own experts' part (:func:`route`, :func:`no_drop_moe`).

    ``top_k``: the capacity path (training with token dropping,
    :func:`top_k_gating`) takes 1 or 2, as the reference's gates do; the
    dropless path (evaluation and serving, :func:`route`) any k up to the
    router's width (8 since PR 45). ``scoring``, the group-limited choice
    (``n_groups`` / ``topk_groups``) and ``routed_scale`` are the dropless
    path's alone: the capacity path refuses any value but
    their defaults (:func:`check_capacity_gate`)."""

    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25     # train capacity (reference default 1.0/1.25)
    eval_capacity_factor: float = 2.0
    min_capacity: int = 4             # reference sharded_moe.py min_capacity
    noisy_gate_policy: Optional[str] = None  # None | 'RSample' | 'Jitter'
    drop_tokens: bool = True
    aux_loss_weight: float = 0.01
    scoring: str = "softmax"          # softmax | sigmoid (DeepSeek-V3's family)
    # group-limited choice: the experts in ``n_groups`` runs of consecutive
    # ids, a group scored by its largest member, the top_k taken inside the
    # ``topk_groups`` best groups; 1 = no groups
    n_groups: int = 1
    topk_groups: int = 1
    routed_scale: float = 1.0         # the renormalised weights times this
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring {self.scoring!r}: softmax or sigmoid")
        if self.n_groups < 1 or self.n_experts % self.n_groups \
                or not 1 <= self.topk_groups <= self.n_groups:
            raise ValueError(
                f"n_groups {self.n_groups} must divide n_experts "
                f"{self.n_experts}, with 1 <= topk_groups "
                f"({self.topk_groups}) <= n_groups")
        if self.top_k > self.topk_groups * (self.n_experts // self.n_groups):
            raise ValueError(
                f"top_k {self.top_k} exceeds the {self.topk_groups} chosen "
                f"groups' experts")
        if self.experts_held is not None:
            first, past = self.experts_held = tuple(
                int(e) for e in self.experts_held)
            if not 0 <= first < past <= self.n_experts:
                raise ValueError(
                    f"experts_held {self.experts_held} is no range inside "
                    f"the router's {self.n_experts} experts")

    @property
    def held(self) -> Tuple[int, int]:
        """(first held expert's router id, how many are held)."""
        first, past = self.experts_held or (0, self.n_experts)
        return first, past - first


def check_capacity_gate(cfg: GateConfig) -> None:
    """What the capacity path (:func:`top_k_gating`: training with token
    dropping) does not implement, refused by message and not ignored."""
    if cfg.top_k not in (1, 2):
        raise NotImplementedError(
            f"the capacity gate takes top_k 1 or 2, got {cfg.top_k}: train "
            "without dropping (drop_tokens=False routes through the "
            "dropless path, any k)")
    if (cfg.scoring != "softmax" or cfg.n_groups != 1
            or cfg.routed_scale != 1.0 or cfg.experts_held is not None):
        raise NotImplementedError(
            "the capacity gate scores by a softmax over all experts and "
            "renormalises the chosen: scoring='sigmoid', n_groups > 1, "
            "routed_scale != 1 and experts_held are the "
            "dropless path's (drop_tokens=False, evaluation, serving)")


def route(logits: jnp.ndarray, cfg: GateConfig
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The dropless path's choice, one function for ``MoELayer.apply`` and
    so for the served step: ``logits`` [S, n_experts] float32 -> (scores
    [S, n_experts], weights [S, top_k], router ids [S, top_k]).

    Scores are a softmax over all experts or a sigmoid of each. With
    ``n_groups`` > 1 a group is scored by its largest member, the
    ``topk_groups`` best groups are kept and the choice is made among
    their experts. The ``top_k`` largest scores are taken (ties to the
    lower id), divided by their sum and multiplied by ``routed_scale``. The defaults are a softmax's renormalised top k,
    operation for operation what this path always computed."""
    scores = jax.nn.softmax(logits, axis=-1) if cfg.scoring == "softmax" \
        else jax.nn.sigmoid(logits)
    pick = scores
    if cfg.n_groups > 1:
        S, G = scores.shape[0], cfg.n_groups
        by_group = jnp.max(scores.reshape(S, G, -1), axis=-1)      # [S, G]
        _, best = jax.lax.top_k(by_group, cfg.topk_groups)
        kept = jnp.zeros((S, G), bool).at[
            jnp.arange(S)[:, None], best].set(True)
        pick = jnp.where(jnp.repeat(kept, cfg.n_experts // G, axis=1),
                         scores, -1.0)       # scores are never negative
    topw, topi = jax.lax.top_k(pick, cfg.top_k)
    topw = topw / jnp.maximum(jnp.sum(topw, axis=-1, keepdims=True), 1e-9)
    if cfg.routed_scale != 1.0:
        topw = topw * cfg.routed_scale
    return scores, topw, topi


def capacity(tokens_per_group: int, cfg: GateConfig, training: bool) -> int:
    if not cfg.drop_tokens:
        # no-drop mode: static shapes force the worst-case bound (every token
        # routed to one expert). The reference grows capacity to the observed
        # max load at runtime (sharded_moe.py drop_tokens=False path); under
        # XLA the conservative static bound is the equivalent guarantee.
        return tokens_per_group
    f = cfg.capacity_factor if training else cfg.eval_capacity_factor
    cap = int(np.ceil(tokens_per_group * f * cfg.top_k / cfg.n_experts))
    return max(cap, cfg.min_capacity)


def _one_hot(idx, n):
    return jax.nn.one_hot(idx, n, dtype=jnp.float32)


def top_k_gating(logits: jnp.ndarray, cfg: GateConfig, cap: int,
                 rng: Optional[jax.Array] = None, training: bool = True
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Compute combine weights + dispatch mask for top-1/top-2 routing.

    logits: [S, E] per-group router logits.
    Returns (combine [S, E, C], dispatch bool [S, E, C], aux_loss scalar).

    Mirrors reference top1gating/top2gating: softmax probs, greedy expert
    choice (optionally noisy), position-in-expert via a cumsum over the
    token dimension, tokens beyond capacity dropped, load-balance loss
    = E * mean(probs_per_expert) . mean(assignment_per_expert).
    """
    check_capacity_gate(cfg)
    S, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    if cfg.noisy_gate_policy == "RSample" and training and rng is not None:
        noisy = logits + jax.random.gumbel(rng, logits.shape)
        idx1 = jnp.argmax(noisy, axis=-1)
    else:
        idx1 = jnp.argmax(probs, axis=-1)
    mask1 = _one_hot(idx1, E)                                  # [S, E]

    # load-balancing aux loss (GShard eq.; reference l_aux in top*gating)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(mask1, axis=0)
    aux = jnp.sum(me * ce) * E

    # position of each token within its expert's queue
    pos1 = jnp.cumsum(mask1, axis=0) - mask1                    # [S, E]
    pos1_tok = jnp.sum(pos1 * mask1, axis=1)                    # [S]
    if cfg.drop_tokens:
        keep1 = pos1_tok < cap
        mask1 = mask1 * keep1[:, None]

    gates1 = jnp.sum(probs * mask1, axis=1)                     # [S]

    if cfg.top_k == 2:
        probs2 = probs * (1.0 - _one_hot(idx1, E))
        idx2 = jnp.argmax(probs2, axis=-1)
        mask2 = _one_hot(idx2, E)
        pos2 = jnp.cumsum(mask2, axis=0) - mask2 + jnp.sum(mask1, axis=0, keepdims=True)
        pos2_tok = jnp.sum(pos2 * mask2, axis=1)
        if cfg.drop_tokens:
            keep2 = pos2_tok < cap
            mask2 = mask2 * keep2[:, None]
        gates2 = jnp.sum(probs * mask2, axis=1)
        denom = jnp.maximum(gates1 + gates2, 1e-9)
        gates1, gates2 = gates1 / denom, gates2 / denom
        combine = (gates1[:, None, None] * mask1[:, :, None] * _one_hot(pos1_tok, cap)[:, None, :]
                   + gates2[:, None, None] * mask2[:, :, None] * _one_hot(pos2_tok, cap)[:, None, :])
    else:
        combine = gates1[:, None, None] * mask1[:, :, None] * _one_hot(pos1_tok, cap)[:, None, :]

    dispatch = combine > 0
    return combine.astype(jnp.float32), dispatch, aux


#: the expert leaves that are operands of ``ragged_dot`` in no_drop_moe
RAGGED_OPERANDS = ("w_gate", "w_up", "w_down")

#: rows an expert past which its product is bound by the MXU and no longer
#: by its matrix's bytes: a v5e's 197 TFLOP/s over 819 GB/s, two operations
#: a bfloat16 element a row (profiling/flops_profiler.DEVICE_PEAKS)
RIDGE_ROWS = 240


def expert_product(path: str, rows: int, n_experts: int) -> str:
    """Which grouped product a compiled serving step holds, ``"kernel"``
    (``ops/pallas/grouped_matmul.py``) or ``"ragged_dot"``, from what the
    trace can see: the step's ``attention_path`` and the product's static
    shape (``rows`` pairs over ``n_experts`` matrices). The kernel where
    both hold: a kernel path (``"gather"``, the path off the TPU and the
    kernel's oracle, keeps ``ragged_dot``), and a product bound by the
    matrices' bytes, a mean of under ``RIDGE_ROWS`` rows an expert;
    whatever the matrix's tiling (``weight_tiles``: one block where it
    fits 4 MiB, else all of K by a tile of N, else tiles of both).
    Measured on a v5e (PERF.md section 6, PR 46): 128 experts of 2048 x
    768, one block each, at 4 / 16 / 64 rows an expert run 2.2 to 3.3 times
    ``ragged_dot``'s speed and their cell's wait a token fell by 60%. Eight
    experts of 4096 x 14336 (tiled N, tiled K) run 1.2 times at 16 rows an
    expert and 1.6 to 2.1 at 64; past the ridge 1.0 to 1.5 at 256 and 0.6
    to 1.4 at 512, which is why the ridge stands. Until PR 47 a third
    clause kept such tiled shapes on ``ragged_dot``: their cell's ticks
    gained 16% (its tail 36%) but its set-up, 28 step programs with two
    more Mosaic kernels each to trace and lower, lost 9% against a bound
    of 10%. Since PR 47 an engine on the tiled attention path holds one
    step program a lane bucket (``inference/ragged.py::_program_pages``),
    the kernels cost that cell two programs' lowering, not 28, and the
    clause is gone (the set-up as read then: PERF.md section 6, PR 47)."""
    return "kernel" if path != "gather" and rows < RIDGE_ROWS * n_experts \
        else "ragged_dot"


def no_drop_moe(x_flat: jnp.ndarray, probs: jnp.ndarray, idx: jnp.ndarray,
                params: Dict[str, Any], activation: str,
                layer: Optional[int] = None,
                path: str = "gather", held_from: Optional[int] = None,
                live: Optional[jnp.ndarray] = None,
                tally: Optional[list] = None) -> jnp.ndarray:
    """Sort-based NO-DROP expert dispatch on grouped GEMMs.

    The TPU analog of FastGen's ``moe_gather``/``moe_scatter`` +
    CUTLASS grouped GEMM (reference
    ``inference/v2/kernels/ragged_ops/{moe_gather,moe_scatter}`` and
    ``kernels/cutlass_ops/moe_gemm``): (token, k) pairs are sorted by
    expert id, each expert's contiguous segment runs through
    ``jax.lax.ragged_dot`` (the MXU grouped GEMM), and outputs
    scatter-add back weighted by the gate. No capacity buffers — no token
    is ever dropped and no [S, E, C] combine tensor exists, so serving
    output is independent of co-scheduled traffic.

    x_flat: [S, d]; probs/idx: [S, k] top-k gate weights / expert ids.

    ``layer``: the layer's place in the stack when the ``RAGGED_OPERANDS``
    arrive unsliced, ``[L, E, K, N]`` (biases stay a layer's own). On the
    TPU ``ragged_dot`` is an operation of its own whose operand has to be
    a whole buffer, so a ``w[layer]`` in front of it is a copy of the
    layer's expert matrices on every call. Instead the stack is viewed as
    ``L*E`` groups (a bitcast) and the layer's E group sizes sit at
    ``[layer*E, layer*E + E)`` of a zero vector: the product walks only
    the tiles that hold rows, so the other layers' matrices are not read
    (on a v5e within 0.4% of the layer's own leaf at 16 to 4096 rows;
    PERF.md, PR 31).

    ``path``: the serving step's ``attention_path``, handed down as to the
    recurrent mixers. With the stack in place, :func:`expert_product`
    decides from it and the product's static shape whether the three
    products are ``ragged_dot``'s or calls of the Pallas kernel over the
    same operands (``ops/pallas/grouped_matmul.py``: gate and up in one
    call, ``silu(g) * u`` formed in float32). ``model.apply`` (training
    without dropping, evaluation, the benchmark's references) and sharded
    serving pass none and keep ``ragged_dot``.

    ``held_from`` (an expert share, ``GateConfig.experts_held``): ``idx``
    holds router ids and the stacks hold the E experts from that id on; a
    pair whose expert is not held, and with ``live`` [S] bool a pair of a
    lane that is not live, goes to no group. Such pairs sort past the last
    group, the product visits only held experts that live lanes reached,
    and their rows add nothing. None (every expert held, every lane
    counted) is the program this always was. ``tally``: a list that takes
    this layer's (experts touched, pairs kept), both int32 scalars, for a
    step that counts them (``inference/ragged.py``).
    """
    S, k = idx.shape
    w = {n: params[n] for n in RAGGED_OPERANDS if n in params}
    E = w["w_up"].shape[-3]
    flat_e = idx.reshape(-1)                          # [S*k]
    kept = None
    if held_from is not None:
        flat_e = flat_e - held_from
        kept = (flat_e >= 0) & (flat_e < E)
        if live is not None:
            kept &= jnp.repeat(live, k)
        flat_e = jnp.where(kept, flat_e, E)           # E: no group, sorts last
    order = jnp.argsort(flat_e)                       # stable: tokens in order
    tok = jnp.repeat(jnp.arange(S), k)[order]         # source token per pair
    xs = x_flat[tok]                                  # moe_gather
    if kept is None:
        group_sizes = jnp.bincount(flat_e, length=E).astype(jnp.int32)
    else:
        group_sizes = jnp.zeros((E,), jnp.int32).at[flat_e].add(
            1, mode="drop")
    if tally is not None:
        tally.append((jnp.sum(group_sizes > 0), jnp.sum(group_sizes)))
    kernel = layer is not None and \
        expert_product(path, S * k, E) == "kernel"
    if layer is not None:
        L = w["w_up"].shape[0]
        w = {n: a.reshape((L * E,) + a.shape[2:]) for n, a in w.items()}
    if kernel:
        from ..ops.pallas.grouped_matmul import grouped_matmul, visits

        # one schedule a layer: its products multiply the same S * k rows
        # by the same groups
        sched = visits(group_sizes, S * k, layer * E)

        def product(rows, name, gated=None):
            return grouped_matmul(
                rows, w[name], sched, None if gated is None else w[gated],
                interpret=path == "pallas_interpret")
    else:
        if layer is not None:
            group_sizes = jnp.pad(group_sizes,
                                  (layer * E, (L - 1 - layer) * E))

        def product(rows, name, gated=None):
            out = jax.lax.ragged_dot(rows, w[name], group_sizes)
            return out if gated is None else \
                jax.nn.silu(out) * jax.lax.ragged_dot(rows, w[gated],
                                                      group_sizes)

    e_sorted = flat_e[order]                          # expert id per row
    if activation == "silu_glu":
        h = product(xs, "w_gate", "w_up")
    else:
        h = product(xs, "w_up")
        if "b_up" in params:
            h = h + params["b_up"][e_sorted].astype(h.dtype)
        h = jax.nn.gelu(h)
    ys = product(h, "w_down")                         # [S*k, d]
    if "b_down" in params:
        ys = ys + params["b_down"][e_sorted].astype(ys.dtype)
    gate = probs.reshape(-1)[order][:, None].astype(ys.dtype)
    ys = ys * gate
    if kept is not None:
        # rows of no group: zeros from the kernel, unspecified elsewhere
        ys = jnp.where(kept[order][:, None], ys, 0)
    return jnp.zeros_like(x_flat).at[tok].add(ys.astype(x_flat.dtype))


class MoELayer:
    """Expert-parallel gated FFN bank.

    Params: {"wg": [d, E], "w_up": [E, d, f], "w_gate": [E, d, f] (glu),
    "w_down": [E, f, d]}. Expert weights shard over ('expert', 'model')
    axes; dispatch einsums produce the all-to-alls under GSPMD. Eval /
    serving routes through :func:`no_drop_moe` — capacity-dropping is a
    training-throughput tradeoff and has no place in inference, where it
    would make a sequence's logits depend on co-scheduled traffic.
    """

    def __init__(self, d_model: int, d_ff: int, gate: GateConfig,
                 activation: str = "silu_glu", use_bias: bool = False,
                 n_shared_experts: int = 0):
        self.d_model, self.d_ff, self.gate, self.activation = d_model, d_ff, gate, activation
        # per-expert biases (Megatron-DeepSpeed MoE experts carry
        # dense_h_to_4h/dense_4h_to_h biases; glu llama-style experts don't)
        self.use_bias = use_bias
        # experts every token takes beside the routed ones (DeepSeek's
        # shared experts): one SwiGLU of width n_shared_experts * d_ff,
        # leaves ws_gate / ws_up / ws_down, unweighted
        self.n_shared_experts = n_shared_experts
        if n_shared_experts and activation != "silu_glu":
            raise NotImplementedError("shared experts are SwiGLU")

    def init(self, rng, dtype=jnp.float32, n_layers: Optional[int] = None) -> Dict[str, Any]:
        # the router is as wide as the layer has experts; the stacks hold
        # the experts held
        R, d, f = self.gate.n_experts, self.d_model, self.d_ff
        E = self.gate.held[1]
        lead = (n_layers,) if n_layers else ()
        k1, k2, k3, k4 = jax.random.split(rng, 4)

        def dense(key, shape, fan_in):
            return (jax.random.normal(key, lead + shape, jnp.float32) / np.sqrt(fan_in)).astype(dtype)

        p = {
            "wg": dense(k1, (d, R), d),
            "w_up": dense(k2, (E, d, f), d),
            "w_down": dense(k3, (E, f, d), f),
        }
        if self.activation == "silu_glu":
            p["w_gate"] = dense(k4, (E, d, f), d)
        if self.n_shared_experts:
            fs = self.n_shared_experts * f
            ks = jax.random.split(jax.random.fold_in(rng, 1), 3)
            p.update(ws_gate=dense(ks[0], (d, fs), d),
                     ws_up=dense(ks[1], (d, fs), d),
                     ws_down=dense(ks[2], (fs, d), fs))
        if self.use_bias:
            p["b_up"] = jnp.zeros(lead + (E, f), dtype)
            p["b_down"] = jnp.zeros(lead + (E, d), dtype)
        return p

    def apply(self, params: Dict[str, Any], x: jnp.ndarray,
              rng: Optional[jax.Array] = None, training: bool = True,
              layer: Optional[int] = None, path: str = "gather",
              live: Optional[jnp.ndarray] = None,
              tally: Optional[list] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """x: [b, s, d] -> (out [b, s, d], aux_loss). Token groups = batch
        rows (group-limited routing like the reference's per-group capacity).
        Eval / no-drop uses the sort-based grouped-GEMM path; ``layer`` and
        ``path`` are no_drop_moe's (the expert matrices arrive as the whole
        stack, from a serving step that says which of its paths it is), as
        are ``live`` [b * s] and ``tally``, which only an expert share
        (``experts_held``) reads."""
        b, s, d = x.shape
        cfg = self.gate
        # device scopes (metadata only): ``router``, ``experts`` and
        # ``shared`` name the layer's parts in a profiler trace
        # (docs/observability.md)
        if not training or not cfg.drop_tokens:
            with jax.named_scope("router"):
                logits = x.astype(jnp.float32) @ params["wg"].astype(jnp.float32)
                probs, topw, topi = route(logits.reshape(b * s, -1), cfg)
                # same load-balance diagnostic as the drop path
                assign = jnp.mean(jax.nn.one_hot(topi[:, 0], cfg.n_experts),
                                  axis=0)
                aux = cfg.n_experts * jnp.sum(jnp.mean(probs, axis=0) * assign)
            share = {} if cfg.experts_held is None else dict(
                held_from=cfg.held[0], live=live, tally=tally)
            with jax.named_scope("experts"):
                out = no_drop_moe(x.reshape(b * s, d), topw, topi, params,
                                  self.activation, layer, path, **share)
            return self._with_shared(out.reshape(b, s, d), params, x), aux
        with jax.named_scope("router"):
            cap = capacity(s, cfg, training)
            if cfg.noisy_gate_policy == "Jitter" and training and rng is not None:
                # multiplicative input jitter (reference multiplicative_jitter,
                # sharded_moe.py): x * U(1-eps, 1+eps) for the router only
                rng, jkey = jax.random.split(rng)
                x_r = x * jax.random.uniform(jkey, x.shape, x.dtype, 0.99, 1.01)
            else:
                x_r = x
            logits = x_r.astype(jnp.float32) @ params["wg"].astype(jnp.float32)  # [b, s, E]

            def per_group(lg, r):
                return top_k_gating(lg, cfg, cap, r, training)

            rngs = jax.random.split(rng, b) if rng is not None else None
            combine, dispatch, aux = jax.vmap(per_group)(
                logits, rngs) if rngs is not None else jax.vmap(lambda lg: per_group(lg, None))(logits)
            aux = jnp.mean(aux)

        with jax.named_scope("experts"):
            # dispatch: [b, s, E, C] x [b, s, d] -> [E, b, C, d]
            disp = dispatch.astype(x.dtype)
            expert_in = jnp.einsum("bsec,bsd->ebcd", disp, x)
            if self.activation == "silu_glu":
                h = jax.nn.silu(jnp.einsum("ebcd,edf->ebcf", expert_in, params["w_gate"])) * \
                    jnp.einsum("ebcd,edf->ebcf", expert_in, params["w_up"])
            else:
                h = jnp.einsum("ebcd,edf->ebcf", expert_in, params["w_up"])
                if "b_up" in params:
                    h = h + params["b_up"][:, None, None, :].astype(h.dtype)
                h = jax.nn.gelu(h)
            expert_out = jnp.einsum("ebcf,efd->ebcd", h, params["w_down"])
            if "b_down" in params:
                expert_out = expert_out + params["b_down"][:, None, None, :].astype(expert_out.dtype)
            out = jnp.einsum("bsec,ebcd->bsd", combine.astype(x.dtype), expert_out)
        return self._with_shared(out, params, x), aux

    def _with_shared(self, out, params, x):
        """``out`` plus the shared experts' output, where the layer has
        them."""
        if not self.n_shared_experts:
            return out
        with jax.named_scope("shared"):
            return out + (jax.nn.silu(x @ params["ws_gate"])
                          * (x @ params["ws_up"])) @ params["ws_down"]

    def partition_specs(self, n_layers: Optional[int] = None,
                        pipe: Optional[str] = None):
        """``pipe``: mesh axis name to shard the stacked-layer leading dim
        over (pipeline stages own their layers' expert banks, matching the
        dense-param placement in Transformer.partition_specs)."""
        from jax.sharding import PartitionSpec as P

        lead = (pipe,) if n_layers else ()
        specs = {
            "wg": P(*lead, None, None),
            "w_up": P(*lead, "expert", None, "model"),
            "w_down": P(*lead, "expert", "model", None),
        }
        if self.activation == "silu_glu":
            specs["w_gate"] = P(*lead, "expert", None, "model")
        if self.use_bias:
            specs["b_up"] = P(*lead, "expert", "model")
            specs["b_down"] = P(*lead, "expert", None)
        if self.n_shared_experts:
            specs.update(ws_gate=P(*lead, None, "model"),
                         ws_up=P(*lead, None, "model"),
                         ws_down=P(*lead, "model", None))
        return specs
