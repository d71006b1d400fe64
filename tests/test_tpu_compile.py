"""Compiles for a described (not attached) TPU v5e, on the CPU test host.

The TPU compiler is installed here and compiles for a chip that is
described, so what Mosaic or XLA:TPU would refuse on the machine with the
chip is refused here first, at no chip time (on-chip-measurement guide §2,
rehearsal 3): a slice off the tiling, too much VMEM, a program over 16 GB,
a kernel that cannot be partitioned. Interpret mode shows none of that.
Nothing runs, so these say nothing about results or speed — the numeric
halves live in ``chip_smoke.py``'s kernels phase, on the chip.

Covered: the kernels of the main path at Mistral-7B widths (flash fwd/bwd;
paged attention bf16, windowed and int8-KV, at a prefill-chunk and a
decode shape; the int4-KV refusal), one whole train step and one ragged
serving step of the smoke model at reduced depth, the four-chip
ZeRO-3 step ``chip_smoke.py --chips 4`` runs, and what the compiled
serving step does to its KV pool (rows written in place, on one chip by
one ``write_kv_pages`` call a layer and with the pool sharded over two by
the scatter; the writer alone at the cells' shapes; the benchmark's
roofline readers tell it from the paged kernel), the 12-layer Olmo-Hybrid step of the
benchmark's cell with both of its caches (fits, copies no leaf and no
weight), the Mixtral cell's step (copies no expert matrix), the Ouro
cell's 48-layer step of four passes (one rolled loop, no pool leaf copied),
how the six serving configurations' decode steps read their stacked
``wq`` / ``wk`` / ``wv`` / ``w_uq`` leaves (in place: no slice written out,
no stack copied),
and the two recurrent kinds' step kernels alone and in their cells' steps
(nine calls each, the state leaf aliased; Granite's inside its rolled loop
over periods, on a leaf of four runs of slots).

One file on purpose: only the xdist worker that gets this file loads
libtpu, inside the module-scoped ``topo`` fixture — never at import.
"""

import os
import re
from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke

HBM_BYTES = 16e9  # one v5e chip (profiling/flops_profiler.DEVICE_PEAKS)
SZ = chip_smoke.Sizes()
HQ, HKV, HD, BLK = SZ.n_heads, SZ.n_kv_heads, SZ.head_dim, SZ.kv_block_size
N_PAGES, PAGES_PER_SEQ = 8192, 256


@pytest.fixture(scope="module")
def topo():
    """A described 2x2 v5e host, with the persistent compile cache off
    around the module: an entry written for a described chip cannot be
    read back without one, and the next compile would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Dispatch code asks ``jax.devices()`` and sees the CPU; the test
    steers it onto its TPU branch (guide §2) — not an option of the
    program."""
    monkeypatch.setattr("deepspeed_tpu.ops.attention._on_tpu", lambda: True)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _place(tree, sharding):
    """Shapes of ``tree`` with ``sharding`` (one, or a matching tree)."""
    if isinstance(sharding, jax.sharding.Sharding):
        return jax.tree_util.tree_map(
            lambda x: _sds(x.shape, x.dtype, sharding), tree)
    return jax.tree_util.tree_map(
        lambda x, s: _sds(x.shape, x.dtype, s), tree, sharding)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


# ----------------------------------------------------------------------
# kernels
def test_flash_forward_compiles(one_chip):
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    q = _sds((1, SZ.kernel_seq, HQ, HD), jnp.bfloat16, one_chip)
    k = _sds((1, SZ.kernel_seq, HKV, HD), jnp.bfloat16, one_chip)
    c = jax.jit(lambda q, k, v: flash_attention(q, k, v, True, None)) \
        .lower(q, k, k).compile()
    assert c.as_text().count("tpu_custom_call") == 1


def test_flash_backward_compiles(one_chip):
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    q = _sds((1, SZ.kernel_seq, HQ, HD), jnp.bfloat16, one_chip)
    k = _sds((1, SZ.kernel_seq, HKV, HD), jnp.bfloat16, one_chip)
    loss = lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, True, None).astype(jnp.float32) ** 2)
    c = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, k).compile()
    assert c.as_text().count("tpu_custom_call") == 3  # fwd, dq, dkv


OLMO_HEADS = (30, 30)   # Olmo-Hybrid-7B's full layers: 30 KV heads, group 1
# the paged kernel's result as the benchmark's readers know it: a 4-D bf16
# array from one custom call (benchmarks/readers/paged_attention_roofline.py);
# the row writer's is a tuple of two pool leaves, which they must not count
_KERNEL_RESULT = re.compile(r"= bf16\[\d+,\d+,\d+,\d+\]\S* custom-call\(")
_WRITER_RESULT = re.compile(r"= \((\w+\[[\d,]+\])\S*, \1\S*\) custom-call\(")
# a recurrent layer's step's (the delta rule's, Mamba-2's): the entries'
# output rows and the state leaf
_STEP_RESULT = re.compile(r"= \(f32\[\d+,\d+,\d+\]\S*, "
                          r"f32\[(\d+,\d+,\d+,\d+)\]\S*\) custom-call\(")
# the scope each kind's step runs under: the path its roofline metric
# (``delta_step_roofline_pct``, ``ssd_step_roofline_pct``) reads
_STEP_SCOPES = r"(linear_attn/delta_step|ssm/ssd_step)"
# the experts' grouped product by its name (``grouped_matmul.N``, what
# ``breakdown.device_ops`` lists): the pairs' rows, 2-D, as XLA:TPU's own
# ``ragged-dot-none.N`` kernels return them
_GROUPED_RESULT = re.compile(
    r"%grouped_matmul[.\d]* = bf16\[\d+,\d+\]\S* custom-call\(")
_RAGGED_DOT = re.compile(r"%ragged-dot[\w.\-]* = ")


def _custom_calls(hlo: str) -> list:
    return [l for l in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in l]


def _writer_calls(hlo: str) -> list:
    """The row writer's calls (``write_kv_pages``): each returns a tuple
    of two leaves of one shape and runs under ``attn/scatter``, the path
    ``loop_device_ms.serve`` reads and ``attn_device_ms.serve`` counts."""
    calls = [l for l in _custom_calls(hlo) if _WRITER_RESULT.search(l)]
    assert all(re.search(r'op_name="[^"]*attn/scatter/[^"]*"', l)
               for l in calls), calls
    return calls


def _step_calls(hlo: str) -> list:
    """The step kernels' calls (``delta_step_slots``, ``ssd_step_slots``):
    each returns the entries' rows and a state leaf, which is also its
    operand (aliased: written where it lies), and runs under its kind's
    scope (``_STEP_SCOPES``)."""
    calls = [l for l in _custom_calls(hlo) if _STEP_RESULT.search(l)]
    for l in calls:
        leaf = f"f32[{_STEP_RESULT.search(l).group(1)}]"
        assert l.count(leaf) >= 2 and "output_to_operand_aliasing" in l, l
        assert re.search(r'op_name="[^"]*' + _STEP_SCOPES + r'/[^"]*"', l), l
    return calls


def _grouped_calls(hlo: str) -> list:
    """The experts' grouped products (``grouped_matmul``): each returns
    the (lane, expert) pairs' rows and runs under ``ffn/experts``, the
    scope ``experts_device_ms.serve`` reads."""
    calls = [l for l in _custom_calls(hlo) if _GROUPED_RESULT.search(l)]
    assert all(re.search(r'op_name="[^"]*ffn/experts/[^"]*"', l)
               for l in calls), calls
    return calls


def _kernel_calls(hlo: str) -> int:
    """The paged kernel's calls; every other kernel of the module is the
    row writer, a recurrent layer's step or the experts' product."""
    calls = _custom_calls(hlo)
    paged = [l for l in calls if _KERNEL_RESULT.search(l)]
    assert len(paged) + len(_writer_calls(hlo)) + len(_step_calls(hlo)) \
        + len(_grouped_calls(hlo)) \
        + len([l for l in calls if _RAGGED_DOT.search(l)]) == len(calls), calls
    return len(paged)


def _paged_args(T, sharding, pool_dtype=jnp.bfloat16, hd_packed=HD,
                heads=(HQ, HKV)):
    hq, hkv = heads
    return dict(
        q=_sds((T, hq, HD), jnp.bfloat16, sharding),
        pool=_sds((N_PAGES + 1, hkv, BLK, hd_packed), pool_dtype, sharding),
        scale=_sds((N_PAGES + 1, hkv, BLK), jnp.float32, sharding),
        tables=_sds((SZ.max_seqs, PAGES_PER_SEQ), jnp.int32, sharding),
        lanes=_sds((T,), jnp.int32, sharding))


@pytest.mark.parametrize("T", [SZ.token_budget, 1024, 64],
                         ids=["prefill_chunk", "lanes1024", "decode"])
@pytest.mark.parametrize("variant", ["bf16", "windowed", "int8_kv", "olmo"])
def test_paged_attention_compiles(one_chip, variant, T):
    """Per-sequence tables + slot indirection, the ragged engine's call
    shape, at every lane bucket the cells run: Mistral's head shape (and
    its windowed and int8-KV forms), and Olmo-Hybrid's."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_attention

    quant = variant == "int8_kv"
    a = _paged_args(T, one_chip, jnp.int8 if quant else jnp.bfloat16,
                    heads=OLMO_HEADS if variant == "olmo" else (HQ, HKV))
    window = 1024 if variant == "windowed" else 0

    def fn(q, kp, vp, tables, pos, slots, ks, vs):
        scales = dict(k_scale=ks, v_scale=vs, kv_bits=8) if quant else {}
        return paged_attention(q, kp, vp, tables, pos, seq_slots=slots,
                               live_pages=PAGES_PER_SEQ, window=window,
                               **scales)

    c = jax.jit(fn).lower(a["q"], a["pool"], a["pool"], a["tables"],
                          a["lanes"], a["lanes"], a["scale"],
                          a["scale"]).compile()
    assert _kernel_calls(c.as_text()) == 1


@pytest.mark.parametrize("hkv,n_seqs,T", [
    (HKV, 64, 64), (HKV, 64, 1024), (HKV, 64, SZ.token_budget),
    (16, 32, 64), (16, 32, 512), (30, 64, 64), (30, 64, 1024)],
    ids=["mistral_decode", "mistral_1024", "mistral_budget", "ouro_decode",
         "ouro_budget", "olmo_decode", "olmo_budget"])
def test_row_writer_compiles(one_chip, hkv, n_seqs, T):
    """``write_kv_pages`` alone at the three cells' leaf shapes and lane
    buckets (tiles of 16, 32 and 64 rows: 2, 3 and 5 page slabs a tile,
    32 / 64 / 120 KB each): the dynamic sublane rotation and the slab
    copies lower, both leaves come back from one call, aliased to the
    donated operands, and no copy of a leaf is made around it."""
    from deepspeed_tpu.ops.pallas.paged_attention import (work_list,
                                                          write_kv_pages)

    leaf = _sds((N_PAGES + 1, hkv, BLK, HD), jnp.bfloat16, one_chip)
    rows = _sds((T, hkv, HD), jnp.bfloat16, one_chip)
    lanes = _sds((T,), jnp.int32, one_chip)

    def fn(k, v, nk, nv, tables, slots, pos):
        return write_kv_pages(k, v, nk, nv, tables,
                              work_list(slots, pos, n_seqs))

    c = jax.jit(fn, donate_argnums=(0, 1)).lower(
        leaf, leaf, rows, rows,
        _sds((n_seqs, PAGES_PER_SEQ), jnp.int32, one_chip), lanes,
        lanes).compile()
    hlo = c.as_text()
    assert len(_custom_calls(hlo)) == 1 and _WRITER_RESULT.search(
        _custom_calls(hlo)[0])
    dims = f"{N_PAGES + 1},{hkv},{BLK},{HD}"
    assert not [i for i in _instructions(hlo).values()
                if i.dims == dims and i.op not in ("parameter", "bitcast",
                                                   "get-tuple-element")]
    assert c.memory_analysis().temp_size_in_bytes < 16e6


@pytest.mark.parametrize("n_slots", [64, 16], ids=["olmo_cell", "few_slots"])
def test_delta_step_kernel_compiles(one_chip, n_slots):
    """``delta_step_slots`` alone at Olmo-Hybrid's state shape (30 heads of
    96 x 192 float32: a whole slot a grid step, 4 x 2.95 MB of VMEM with
    the minor 192 padded to 256): the column picks, the dynamic row loads
    and stores and the SMEM gates lower; the state leaf comes back aliased
    to the donated operand, in the default tiled layout, and nothing
    copies it."""
    from deepspeed_tpu.ops.gated_delta import runs_of
    from deepspeed_tpu.ops.pallas.gated_delta import (delta_step_slots,
                                                      head_block)

    H, dk, dv, T = 30, 96, 192, 64
    assert head_block(H, dk, dv) == 30
    N = min(n_slots, T)
    f32 = lambda *shape: _sds(shape, jnp.float32, one_chip)
    lanes = _sds((T,), jnp.int32, one_chip)

    def fn(q, k, v, g, beta, state, slots, pos):
        return delta_step_slots(q, k, v, g, beta, state,
                                runs_of(slots, pos, n_slots).steps)

    c = jax.jit(fn, donate_argnums=(5,)).lower(
        f32(N, H, dk), f32(N, H, dk), f32(N, H, dv), f32(N, H), f32(N, H),
        f32(n_slots + 1, H, dk, dv), lanes, lanes).compile()
    hlo = c.as_text()
    assert len(_custom_calls(hlo)) == 1
    dims = f"{n_slots + 1},{H},{dk},{dv}"
    call = _custom_calls(hlo)[0]
    assert _STEP_RESULT.search(call).group(1) == dims
    assert "output_to_operand_aliasing" in call
    leaf = [i for i in _instructions(hlo).values() if i.dims == dims]
    assert {i.layout for i in leaf} == {"3,2,1,0"}
    assert not [i for i in leaf if i.op not in ("parameter", "bitcast",
                                                "get-tuple-element",
                                                "custom-call")]
    assert c.memory_analysis().temp_size_in_bytes < 4e6


@pytest.mark.parametrize("period", [None, "traced"],
                         ids=["flat_leaf", "rolled_leaf"])
def test_ssd_step_kernel_compiles(one_chip, period):
    """``ssd_step_slots`` alone at granite-4.0-h-micro's state shape (64
    heads of 64 x 128 float32: a whole slot a grid step, 4 x 2 MiB of VMEM,
    nothing padded), on a leaf of one run of 65 slots and on the cell's
    rolled leaf of four, the period's ``base`` a traced scalar inside a
    ``fori_loop`` that carries the leaf: the column picks, the lane
    reductions, the group's rows by a dynamic index and the SMEM decay
    lower; the state leaf comes back aliased to the donated operand, in the
    default tiled layout, and nothing copies or slices it, inside the loop
    or outside."""
    from deepspeed_tpu.ops.gated_delta import runs_of
    from deepspeed_tpu.ops.pallas.gated_delta import head_block
    from deepspeed_tpu.ops.pallas.mamba2 import ssd_step_slots

    H, P, N, G, S, T = 64, 64, 128, 1, 64, 64
    periods = 4 if period else 1
    assert head_block(H, P, N) == 64
    f32 = lambda *shape: _sds(shape, jnp.float32, one_chip)
    lanes = _sds((T,), jnp.int32, one_chip)

    def fn(x, B, C, dt, g, D, state, slots, pos):
        steps = runs_of(slots, pos, S).steps
        if not period:
            return ssd_step_slots(x, B, C, dt, g, D, state, steps)

        def one_period(t, carry):
            y, state = ssd_step_slots(x, B, C, dt, g, D, carry[1], steps,
                                      t * (S + 1))
            return carry[0] + y, state

        return jax.lax.fori_loop(0, periods, one_period,
                                 (jnp.zeros_like(x), state))

    c = jax.jit(fn, donate_argnums=(6,)).lower(
        f32(T, H, P), f32(T, G, N), f32(T, G, N), f32(T, H), f32(T, H),
        f32(H), f32(periods * (S + 1), H, P, N), lanes, lanes).compile()
    hlo = c.as_text()
    assert len(_custom_calls(hlo)) == 1
    assert (len(re.findall(r" while\(", hlo)) == 1) == bool(period)
    dims = f"{periods * (S + 1)},{H},{P},{N}"
    call = _custom_calls(hlo)[0]
    assert _STEP_RESULT.search(call).group(1) == dims
    assert "output_to_operand_aliasing" in call
    leaf = [i for i in _instructions(hlo).values() if i.dims == dims]
    assert {i.layout for i in leaf} == {"3,2,1,0"}
    assert not [i for i in leaf if i.op not in ("parameter", "bitcast",
                                                "get-tuple-element",
                                                "custom-call")]
    assert c.memory_analysis().temp_size_in_bytes < 4e6


@pytest.mark.parametrize("shape,rows,form", [
    ("sdar", 512, "gated"), ("sdar", 2048, "gated"), ("sdar", 8192, "gated"),
    ("sdar", 512, "down"), ("sdar", 2048, "down"), ("sdar", 8192, "down"),
    ("mixtral", 128, "gated"), ("mixtral", 128, "down")])
def test_grouped_matmul_compiles(one_chip, shape, rows, form):
    """``grouped_matmul`` alone at the two cells' shapes: SDAR's 128
    experts of 2048 x 768 whole in a block (3 MiB, gate and up 4 x 3 MiB
    double-buffered beside a 128-row tile) for the 64 / 256 / 1,024-lane
    programs' 512 / 2,048 / 8,192 rows, Mixtral's 8 of 4096 x 14336 in
    blocks of 4096 x 512 (up) and 2048 x 1024 under a float32 accumulator
    (down) at its decode step's 128 rows (the tiled forms, which its 64-
    and 256-lane programs hold: ``expert_product``): Mosaic takes the
    prefetched
    schedule, the index maps and the masked stores inside the VMEM the
    call asks for, the stack is an operand as it lies (no slice of it, no
    copy) and the call brings no temporary beside its schedule."""
    from deepspeed_tpu.ops.pallas.grouped_matmul import (grouped_matmul,
                                                         visits,
                                                         weight_tiles)

    E, K, N, L = {"sdar": (128, 2048, 768, 7),
                  "mixtral": (8, 4096, 14336, 2)}[shape]
    if form == "down":
        K, N = N, K
    want = {("sdar", "gated"): (2048, 768), ("sdar", "down"): (768, 2048),
            ("mixtral", "gated"): (4096, 512),
            ("mixtral", "down"): (2048, 1024)}[shape, form]
    assert weight_tiles(K, N, jnp.bfloat16) == want
    bf16 = lambda *dims: _sds(dims, jnp.bfloat16, one_chip)

    def fn(xs, w, w2, sizes):
        return grouped_matmul(xs, w, visits(sizes, rows, (L - 1) * E),
                              w2 if form == "gated" else None)

    c = jax.jit(fn).lower(bf16(rows, K), bf16(L * E, K, N),
                          bf16(L * E, K, N),
                          _sds((E,), jnp.int32, one_chip)).compile()
    hlo = c.as_text()
    call, = _custom_calls(hlo)
    assert _GROUPED_RESULT.search(call) and f"bf16[{rows},{N}]" in call
    stack = f"{L * E},{K},{N}"
    assert {i.op for i in _instructions(hlo).values() if i.dims == stack} \
        <= {"parameter"}
    assert c.memory_analysis().temp_size_in_bytes < 1e6


def test_paged_int4_kv_refuses_before_the_compiler(one_chip):
    """The v5e compiler spends minutes on the int4 unpack and then fails
    with RESOURCE_EXHAUSTED (vmem) even at T=16 (ROADMAP.md S4): the
    kernel refuses by name while tracing, without invoking it."""
    from deepspeed_tpu.ops.pallas.paged_attention import (
        Int4KVKernelUnsupported, paged_attention)

    a = _paged_args(64, one_chip, jnp.uint8, HD // 2)
    fn = lambda q, kp, vp, t, p, s, ks, vs: paged_attention(
        q, kp, vp, t, p, seq_slots=s, k_scale=ks, v_scale=vs, kv_bits=4)
    with pytest.raises(Int4KVKernelUnsupported, match="RESOURCE_EXHAUSTED"):
        jax.jit(fn).lower(a["q"], a["pool"], a["pool"], a["tables"],
                          a["lanes"], a["lanes"], a["scale"], a["scale"])


def test_int4_kv_engine_refuses_at_construction_on_tpu(on_tpu):
    """No minutes-long hang at server start, no silent gather path."""
    from deepspeed_tpu.inference.ragged import (RaggedConfig,
                                                RaggedInferenceEngine)
    from deepspeed_tpu.models import Llama
    from deepspeed_tpu.ops.pallas.paged_attention import \
        Int4KVKernelUnsupported

    model = Llama("tiny", n_layers=1, d_model=256, n_heads=2, n_kv_heads=2,
                  vocab_size=64, max_seq_len=64)
    cfg = lambda q: RaggedConfig(token_budget=16, max_seqs=2, n_kv_blocks=4,
                                 max_context=64, kv_quant=q)
    with pytest.raises(Int4KVKernelUnsupported):
        RaggedInferenceEngine(model, cfg("int4"), params={})
    assert RaggedInferenceEngine(model, cfg("int8"),
                                 params={}).attention_path == "pallas"


# ----------------------------------------------------------------------
# whole programs of the smoke model
def compile_train_step(devices, n_layers: int, batch: int, zero_stage: int):
    """The engine's fused GSPMD train step (``TrainEngine._build_train_step``
    with ``_update``: bf16 compute copy of fp32 masters, grads constrained
    to the ZeRO grad shardings, global-norm clip, AdamW, state donated and
    pinned to its shardings) rebuilt from the engine's own parts over
    ``jax.eval_shape`` shapes — the engine itself places real arrays on
    real devices, which a described chip cannot hold."""
    from deepspeed_tpu.config import Config, MeshConfig
    from deepspeed_tpu.parallel.mesh import Topology
    from deepspeed_tpu.parallel.zero import ZeroShardingRules
    from deepspeed_tpu.runtime.engine import _cast_tree, global_norm
    from deepspeed_tpu.runtime.optimizers import build_optimizer

    cfg = Config.from_any(chip_smoke.train_config(SZ, batch, zero_stage))
    topo = Topology.build(MeshConfig(data=len(devices)), devices=devices)
    model = chip_smoke.smoke_model(SZ, n_layers).bind_topology(topo)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    rules = ZeroShardingRules(topo, cfg.zero)
    tp_specs = model.partition_specs(shapes, topo)
    p_sh = rules.param_shardings(shapes, tp_specs)
    g_sh = rules.grad_shardings(shapes, tp_specs)
    opt = build_optimizer(cfg.optimizer.type, cfg.optimizer.params)
    o_shapes = jax.eval_shape(opt.init, shapes)
    o_sh = rules.opt_state_shardings(o_shapes, shapes, tp_specs)
    repl = topo.replicated()

    def train_step(params, opt_state, rng, batch):
        def loss_fn(p):
            return model.loss(_cast_tree(p, jnp.bfloat16), batch,
                              rng).astype(jnp.float32)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.lax.with_sharding_constraint(grads, g_sh)
        with jax.named_scope("optimizer"):      # the engine's name for it
            gnorm = global_norm(grads)
            factor = jnp.minimum(1.0, cfg.gradient_clipping / (gnorm + 1e-6))
            grads = jax.tree_util.tree_map(lambda g: g * factor, grads)
            updates, new_opt = opt.update(grads, opt_state, params)
            new_params = jax.tree_util.tree_map(lambda p, u: p + u, params,
                                                updates)
        return new_params, new_opt, loss

    tokens = _sds((batch, SZ.train_seq), jnp.int32, topo.batch_sharding(2))
    return jax.jit(train_step, donate_argnums=(0, 1),
                   out_shardings=(p_sh, o_sh, repl)).lower(
        _place(shapes, p_sh), _place(o_shapes, o_sh),
        _sds((2,), jnp.uint32, repl), {"input_ids": tokens}).compile()


def test_train_step_compiles_and_fits_one_chip(topo, on_tpu):
    """The step ``chip_smoke.py``'s train phase takes, depth and batch as
    it runs them: flash fwd + bwd kernels inside scan + remat, within one
    chip's HBM."""
    c = compile_train_step(topo.devices[:1], SZ.train_layers,
                           SZ.train_batch, zero_stage=0)
    assert c.as_text().count("tpu_custom_call") >= 3
    assert _device_bytes(c) < HBM_BYTES, _device_bytes(c)


@pytest.fixture(scope="module")
def zero3_on_four(topo):
    """``chip_smoke.py --chips 4``'s step, compiled once for the two tests
    that read it."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr("deepspeed_tpu.ops.attention._on_tpu", lambda: True)
        return compile_train_step(topo.devices, SZ.zero3_layers,
                                  SZ.zero3_batch, zero_stage=3)


def test_zero3_step_on_four_chips_compiles(topo, on_tpu, zero3_on_four):
    """``chip_smoke.py --chips 4``'s program: ZeRO-3 over data=4 is GSPMD
    placement — gathers for the weights, reduce-scatters for the
    gradients — with the flash kernel inside the model's shard_map, and a
    quarter of the one-chip step's state on each device."""
    four = zero3_on_four
    hlo = four.as_text()
    assert "all-gather" in hlo and "reduce-scatter" in hlo
    assert hlo.count("tpu_custom_call") >= 3
    one = compile_train_step(topo.devices[:1], SZ.zero3_layers,
                             SZ.zero3_batch, zero_stage=0)
    assert _device_bytes(one) < HBM_BYTES, _device_bytes(one)
    state = lambda c: c.memory_analysis().argument_size_in_bytes
    assert state(four) < 0.30 * state(one), (state(four), state(one))


def test_zero3_update_sends_nothing_and_the_step_stays_under_its_plan(
        zero3_on_four):
    """The chip's own partitioner on the four-chip step: with the moments
    where their gradients lie (``opt_state_shardings`` takes the model's
    specs) no collective but the norm's scalar all-reduce is under
    ``optimizer``, and what a chip sends a step is under the stage's plan
    (the compute copy gathered twice in bfloat16, the gradients
    reduce-scattered once, counted in float32 as ``TrainEngine.zero_plan``
    counts them). With the moments cut by their shapes alone, PR 55's
    rule, this step holds 26 ``all-to-all``, 24 of them float32 stacks of
    16.8 to 131 MB under ``optimizer``, and sends 2.80 GB a chip of which
    1.39 are all-to-alls (at this depth half the parameters are the
    embedding and the head, which the forward gathers once or not at all,
    so even that is under this plan of 2.88; at the benchmark's eight
    layers it is not: 13.76 against 12.04 on the chip, PERF.md section 6,
    PR 55). What stays is the embedding's lookup and its gradient's
    scatter-add, an ``all-to-all`` of 16.8 MB each: 1.44 GB sent."""
    from benchmarks.readers.named_scope_device import under
    from deepspeed_tpu.profiling import collectives as coll

    found = coll.catalogue(zero3_on_four.as_text())
    ours = [c for c in found if under(c.op_name, ["optimizer"])]
    assert [c.kind for c in ours] == ["all-reduce"], ours
    assert ours[0].bytes <= 64
    assert sum(c.kind == "all-to-all" for c in found) == 2
    count = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(
        jax.eval_shape(chip_smoke.smoke_model(SZ, SZ.zero3_layers).init,
                       jax.random.PRNGKey(0))))
    share = lambda b: b * 3 // 4                # the ring's (n - 1) / n
    plan = 2 * share(count * 2) + share(count * 4)
    sent = sum(c.sent_bytes * c.runs for c in found)
    assert 0.4 * plan < sent < 0.6 * plan, (sent, plan)
    exchanged = sum(c.sent_bytes * c.runs for c in found
                    if c.kind == "all-to-all")
    assert exchanged < 0.02 * sent, (exchanged, sent)


def compile_ragged_step(device_sharding, n_layers: int, T: int,
                        live_pages: int, n_kv_blocks: int = 1024,
                        kv_quant: str = "none", tp_topo=None, sz=SZ):
    """``RaggedInferenceEngine``'s own jitted SplitFuse step, lowered
    against shapes: the engine is built with no weights (the step takes
    them as an argument) and a small host-side pool. ``tp_topo`` (a
    ``Topology`` over described chips with a 'model' axis) gives the
    tensor-parallel step: the engine cannot place a pool on a described
    chip, so it is built unsharded and handed the mesh before it traces."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.inference.ragged import (RaggedConfig,
                                                RaggedInferenceEngine)

    model = chip_smoke.smoke_model(sz, n_layers)
    eng = RaggedInferenceEngine(
        model, RaggedConfig(token_budget=sz.token_budget,
                            max_seqs=sz.max_seqs, kv_block_size=BLK,
                            n_kv_blocks=n_kv_blocks,
                            max_context=sz.max_context, kv_quant=kv_quant),
        params={})
    assert eng.attention_path == "pallas"
    params = jax.eval_shape(partial(model.init, dtype=jnp.bfloat16),
                            jax.random.PRNGKey(0))
    if tp_topo is None:
        p_sh = pool_sh = device_sharding
    else:
        eng.topo, eng._tp_size = tp_topo, tp_topo.model_parallel_size
        mesh = tp_topo.mesh
        p_sh = jax.tree_util.tree_map(
            lambda sp: NamedSharding(mesh, sp),
            model.partition_specs(params, tp_topo),
            is_leaf=lambda x: isinstance(x, P))
        pool_sh = jax.tree_util.tree_map(
            lambda x: NamedSharding(
                mesh, P(None, "model", *(None,) * (x.ndim - 2))),
            eng.kv_pool)
    lanes = _sds((T,), jnp.int32, device_sharding)
    return eng._build_step().lower(
        _place(params, p_sh), _place(eng.kv_pool, pool_sh), lanes, lanes,
        lanes, _sds((SZ.max_seqs, eng.max_pages), jnp.int32, device_sharding),
        _sds((SZ.max_seqs,), jnp.int32, device_sharding),
        live_pages).compile()


@pytest.mark.parametrize("heads", [(HQ, HKV), OLMO_HEADS],
                         ids=["mistral", "olmo"])
@pytest.mark.parametrize("T,live_pages",
                         [(SZ.token_budget, 128), (1024, 128), (64, 128)],
                         ids=["prefill_chunk", "lanes1024", "decode"])
def test_ragged_step_compiles(one_chip, on_tpu, T, live_pages, heads):
    """One kernel call a layer, its result 4-D bf16, and one call of the
    row writer in front of it, at both head shapes the cells serve (Olmo's:
    d_model 3840 = 30 x 128, group 1)."""
    from dataclasses import replace

    n_layers = 2  # reduced from chip_smoke's serve depth: compile time
    sz = replace(SZ, n_heads=heads[0], n_kv_heads=heads[1],
                 d_model=heads[0] * HD)
    c = compile_ragged_step(one_chip, n_layers, T, live_pages, sz=sz)
    assert _kernel_calls(c.as_text()) == n_layers
    assert len(_writer_calls(c.as_text())) == n_layers


def test_step_fn_takes_the_benchmark_warm_up_call(monkeypatch):
    """``benchmarks/runners/serve_open_loop.py::warm`` calls the engine's
    jitted step with eight positional arguments on a batch with no live
    lane, once a (lanes, live pages) shape, unpacks two results, and counts
    on ``put`` finding every program compiled: a PR may not edit that file,
    and a program compiled inside a measured window fails the run. Run
    here on the CPU (kernel in interpret mode, head_dim 128: the grid over
    query tiles with no tile) with the runner's own function. On that grid
    the page bucket is no key of a program (``_program_pages``): the six
    shapes the runner warms are the two lane buckets' programs, and the
    ones ``put`` runs whatever its live bucket.

    The window's ticks are the server's, which asks the engine for token
    ids: they must run the very programs that call compiled. So the
    runner's ``TracedEngine`` goes around the warmed engine and a
    ``ServingEngine`` on it, as ``Served`` builds them, and two requests
    are served to the end under the event ``harness.CompileCounter``
    counts: nothing compiles, every tick is a ``put`` the wrapper
    recorded, and each brings back ``4 * max_seqs`` bytes."""
    from benchmarks.harness import BACKEND_COMPILE_EVENT
    from benchmarks.runners.serve_open_loop import TracedEngine, warm
    from deepspeed_tpu.inference import ragged as ragged_mod
    from deepspeed_tpu.inference.ragged import (RaggedConfig,
                                                RaggedInferenceEngine)
    from deepspeed_tpu.models import Llama
    from deepspeed_tpu.serving import ServingEngine

    monkeypatch.setenv("DST_RAGGED_FORCE_PALLAS", "interpret")
    model = Llama("tiny", n_layers=2, d_model=256, n_heads=2, n_kv_heads=1,
                  vocab_size=128, max_seq_len=128, use_flash=False,
                  remat=False)
    eng = RaggedInferenceEngine(
        model, RaggedConfig(token_budget=96, max_seqs=4, kv_block_size=16,
                            n_kv_blocks=32, max_context=128,
                            dtype=jnp.float32), rng=jax.random.PRNGKey(0))
    assert eng.attention_path == "pallas_interpret"
    assert eng._buckets == [64, 96]
    pool_before = jax.tree_util.tree_map(np.asarray, eng.kv_pool)
    shapes = [(lanes, pages) for lanes in eng._buckets for pages in (1, 2, 4)]
    warm(eng, shapes)
    # nothing live: every pool page but the scratch sink is as it was
    for a, b in zip(jax.tree_util.tree_leaves(pool_before),
                    jax.tree_util.tree_leaves(eng.kv_pool)):
        np.testing.assert_array_equal(a[:-1], np.asarray(b)[:-1])
    compiled = eng._step_fn._cache_size()
    assert compiled == len(eng._buckets) < len(shapes)
    rows = eng.put([1, 2], [list(range(1, 40)), [5, 6, 7]])   # 64 lanes, 4 pages
    assert np.isfinite(rows).all()
    rows = eng.put([1, 2], [[9], [9]])                          # 64 lanes, 4 pages
    assert np.isfinite(rows).all()
    assert eng._step_fn._cache_size() == compiled
    eng.flush([1, 2])

    # the window: the server's ticks, in the ids form, on the same programs
    compiles, fetched = [], []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(kw.get("fun_name"))
        if event == BACKEND_COMPILE_EVENT else None)
    real = ragged_mod.annotate

    def annotate(name, **attrs):
        if name == "ragged.fetch":
            fetched.append(attrs["bytes"])
        return real(name, **attrs)

    monkeypatch.setattr(ragged_mod, "annotate", annotate)
    traced = TracedEngine(eng)
    srv = ServingEngine(traced, {"policy": "fcfs", "max_queue": 8},
                        start=False)
    del compiles[:]
    # 105 prompt tokens against a budget of 96: one prompt is split over two
    # ticks (a -1 in the first); no context passes the 4 warmed pages
    reqs = [srv.submit(list(range(1, 56)), max_new_tokens=5),
            srv.submit(list(range(1, 51)), max_new_tokens=5)]
    ticks = 0
    while not all(r.is_terminal for r in reqs):
        srv._tick()
        ticks += 1
        assert ticks < 50
    srv.close()
    assert [len(r.tokens) for r in reqs] == [5, 5]
    assert compiles == []
    assert eng._step_fn._cache_size() == compiled
    assert len(traced.calls) == len(fetched) == srv._tick_count
    assert set(fetched) == {4 * eng.config.max_seqs}


# ----------------------------------------------------------------------
# the ragged step writes its new K/V rows into the pool in place
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\](?:\{([\d,]*))?\S* "
                    r"([\w\-]+)\(([^)]*)\)")


class Instr(NamedTuple):
    """One array-valued instruction of a compiled module's text."""
    dtype: str          # "bf16"
    dims: str           # "4097,8,16,128"
    layout: str         # minor to major, "3,2,1,0"
    op: str             # opcode
    operands: list      # names
    line: str


def _instructions(hlo: str) -> dict:
    out = {}
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m:
            name, dtype, dims, layout, op, args = m.groups()
            out[name] = Instr(dtype, dims, layout or "", op,
                              re.findall(r"%([\w.\-]+)", args), line)
    return out


POOL_PAGES = 4096  # the benchmark's serving cells hold 4096 pages


def _pool_faults(hlo: str, dtype: str = "bf16", heads: int = HKV,
                 leaf_pages: int = POOL_PAGES + 1) -> list:
    """What a compiled ragged step does to its KV pool's payload leaves
    (``dtype[leaf_pages, heads, BLK, HD]`` on a device) beyond writing
    rows into them. Empty when every value of that shape keeps the layout
    of the donated parameter (the kernel's, row-major), nothing copies or
    gathers a leaf, the row writer (``write_kv_pages``: a Pallas call that
    returns a tuple of both leaves; one chip, bf16) is handed that
    parameter or a bitcast of it, and every leaf the paged kernel reads is
    a row write's own result on the parameter: the writer's, or the
    scatter's (a fusion: a quantized pool, tensor parallelism)."""
    shape = (leaf_pages, heads, BLK, HD)
    dims, n_elements = ",".join(map(str, shape)), int(np.prod(shape))
    ins = _instructions(hlo)
    is_leaf = lambda i: (i.dtype, i.dims) == (dtype, dims)
    faults = []
    for name, i in ins.items():
        if is_leaf(i) and i.layout != "3,2,1,0":
            faults.append(f"{i.op} {name}: a pool leaf in layout "
                          f"{{{i.layout}}}")
        if i.op in ("copy", "all-gather", "all-to-all", "collective-permute") \
                and i.dtype == dtype and i.dims \
                and np.prod(list(map(int, i.dims.split(",")))) == n_elements:
            faults.append(f"{i.op} {name}: moves a whole pool leaf")

    def source(name):  # through bitcasts, to what made the bytes
        while name in ins and ins[name].op in ("bitcast",
                                               "get-tuple-element"):
            name = ins[name].operands[0]
        return name

    opcode = lambda name: ins[name].op if name in ins else "tuple"
    leaves_of = lambda names: [o for o in set(names)
                               if o in ins and is_leaf(ins[o])]
    # the writer's calls are tuple-valued, so ``ins`` does not hold them
    writers = {}
    for line in _writer_calls(hlo):
        name, args = re.match(r"\s*(?:ROOT )?%(\S+) = .*? custom-call\("
                              r"([^)]*)\)", line).groups()
        writers[name] = re.findall(r"%([\w.\-]+)", args)
        for o in leaves_of(writers[name]):
            if opcode(source(o)) != "parameter":
                faults.append(f"writer {name} is handed {o}: made by "
                              f"{opcode(source(o))} {source(o)}")
    kernels = [i for i in ins.values()
               if i.op == "custom-call" and "tpu_custom_call" in i.line]
    for k in kernels:
        for o in leaves_of(k.operands):
            by = source(o)
            if by in writers:
                continue
            origin = source(ins[by].operands[0]) \
                if ins[by].operands else by
            if opcode(by) != "fusion" or opcode(origin) != "parameter":
                faults.append(f"kernel reads {o}: made by {opcode(by)} "
                              f"{by} from {opcode(origin)} {origin}")
    return faults + ([] if kernels else ["no paged kernel in the module"])


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
@pytest.mark.parametrize("T", [64, SZ.token_budget],
                         ids=["decode", "prefill_chunk"])
def test_ragged_step_writes_pool_in_place(one_chip, on_tpu, T, kv_quant):
    """No operation of the compiled step costs what the pool weighs: the
    new rows are written into the donated leaves in the kernel's own
    layout, by one ``write_kv_pages`` call a layer, or for the quantized
    pool by ``write_kv_rows``, a scatter a leaf. With the KV-head axis a
    window of the scatter the same compile holds two transposing copies a
    leaf (eight at two layers, 187 MB of temporaries at the decode
    shape)."""
    c = compile_ragged_step(one_chip, 2, T, 128, n_kv_blocks=POOL_PAGES,
                            kv_quant=kv_quant)
    hlo = c.as_text()
    assert _pool_faults(hlo, "s8" if kv_quant == "int8" else "bf16") == []
    assert len(_writer_calls(hlo)) == (0 if kv_quant == "int8" else 2)
    if kv_quant == "int8":
        # a scale leaf [pages, hkv, block] (2 MB) is laid out {0,2,1} in
        # HBM by the TPU runtime itself (16 minor elements would pad to
        # 128): its rows are written in that layout, and ONE copy a leaf
        # brings it to the kernel's. With the head axis a window it was
        # three (in, to the kernel, back out). PERF.md section 7.
        scale = ("f32", f"{POOL_PAGES + 1},{HKV},{BLK}")
        copies = [i for i in _instructions(hlo).values()
                  if i.op == "copy" and (i.dtype, i.dims) == scale]
        assert len(copies) <= 2 * 2, [i.line[:120] for i in copies]
    if T == 64:
        leaf_bytes = (POOL_PAGES + 1) * HKV * BLK * HD \
            * (1 if kv_quant == "int8" else 2)
        assert c.memory_analysis().temp_size_in_bytes < leaf_bytes


# ----------------------------------------------------------------------
# Olmo-Hybrid-7B as the benchmark serves it: 12 layers, two kinds of cache
@lru_cache(maxsize=None)
def compile_cell_step(config: str, device_sharding, T: int, live_pages: int,
                      n_kv_blocks: int = 0):
    """The SplitFuse step of ``benchmarks/configs/<config>.json`` at its
    file's depth, with the pools its ``engine`` asks for (Olmo-Hybrid:
    4096 pages over the 3 full layers, the state pool of 64 slots over the
    9 linear ones), or with ``n_kv_blocks`` pages where the file's
    ``max_kv_blocks`` is a cap the chip's memory cuts (Ouro). The engine
    is built under ``eval_shape``: its pools are shapes, no byte is held."""
    import json

    from benchmarks import harness
    from deepspeed_tpu.inference.ragged import (RaggedConfig,
                                                RaggedInferenceEngine)

    cfg = json.load(open(os.path.join(harness.HERE, "configs",
                                      config + ".json")))
    e = cfg["engine"]
    model = harness.find("architectures", cfg["architecture"]).build(
        cfg, cfg["num_hidden_layers"])
    made = {}

    def build():
        made["engine"] = RaggedInferenceEngine(
            model, RaggedConfig(token_budget=e["token_budget"],
                                max_seqs=e["max_seqs"],
                                kv_block_size=e["kv_block_size"],
                                n_kv_blocks=n_kv_blocks
                                or e["max_kv_blocks"],
                                max_context=e["max_context"]), params={})
        return made["engine"].kv_pool

    pool = jax.eval_shape(build)
    eng = made["engine"]
    assert eng.attention_path == "pallas"
    params = jax.eval_shape(partial(model.init, dtype=jnp.bfloat16),
                            jax.random.PRNGKey(0))
    lanes = _sds((T,), jnp.int32, device_sharding)
    compiled = eng._build_step().lower(
        _place(params, device_sharding), _place(pool, device_sharding),
        lanes, lanes, lanes,
        _sds((e["max_seqs"], eng.max_pages), jnp.int32, device_sharding),
        # the rows the head reads: one a slot, or a block's (block diffusion)
        _sds((e["max_seqs"],) + ((eng.block_length,)
                                 if eng.block_length > 1 else ()),
             jnp.int32, device_sharding),
        live_pages).compile()
    return compiled, model.config, e


def _entry_instructions(hlo: str):
    """(name, dtype, dims, opcode, line) of the ENTRY computation's
    array-valued instructions (what runs as an operation of its own, not
    inside a fusion)."""
    entry = hlo[hlo.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    pat = re.compile(r"^\s*(?:ROOT )?%(\S+) = \(?(\w+)\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(")
    for line in entry.splitlines():
        m = pat.match(line)
        if m:
            yield m.groups() + (line,)


@pytest.mark.parametrize("T", [64, 1024], ids=["decode", "prefill_chunk"])
def test_olmo_hybrid_step_fits_and_copies_no_leaf(one_chip, on_tpu, T):
    """Rehearsal 3 for the cell ``olmo-hybrid-7b.reason``: the 12-layer
    step at the decode shape and at a 1024-lane shape fits one chip beside
    its pools; the paged kernel and the row writer run in the 3 full
    layers only; each state leaf is written by one call of the delta-rule
    step kernel a layer, which takes the leaf as an operand and returns it
    aliased (where nine ``f32`` fusions over the whole leaf were, PR 41),
    and nothing copies, transposes, slices or selects a pool leaf or a
    state leaf in the entry computation (the chunk loop's ``while`` updates
    one slot in place); and no weight matrix is copied
    out of its stack (the per-layer slices of the three stacks, common,
    ``full`` and ``linear``, are read in place by the products that use
    them: what Mixtral's expert stacks pay 17 ms a tick for, PERF.md
    section 5, this layout does not pay)."""
    compiled, c, e = compile_cell_step("olmo-hybrid-7b", one_chip, T, 128)
    assert _device_bytes(compiled) < 15.75e9
    hlo = compiled.as_text()
    assert _kernel_calls(hlo) == len(c.layers_of("full")) == 3
    assert len(_writer_calls(hlo)) == 3      # K and V of a full layer
    state = f"{e['max_seqs'] + 1},30,96,192"
    rows = f"{e['max_seqs'] + 1},3,11520"
    page = f"{e['max_kv_blocks'] + 1},30,{e['kv_block_size']},128"
    moved = [(op, dims, name) for name, dt, dims, op, line
             in _entry_instructions(hlo)
             if op in ("copy", "transpose", "slice", "dynamic-slice",
                       "gather", "concatenate", "select")
             and dims in (state, rows, page)]
    assert not moved, moved
    steps = _step_calls(hlo)
    assert len(steps) == len(c.layers_of("linear")) == 9
    assert all(f"f32[{state}]" in l for l in steps)
    fusions = [name for name, dt, dims, op, _ in _entry_instructions(hlo)
               if (dt, dims, op) == ("f32", state, "fusion")]
    assert not fusions, fusions
    # a weight matrix has at least hidden x (linear heads x key dim)
    # elements; S(1) marks the compiler's own prefetch of an operand into
    # on-chip memory, which is no copy of the layout's making
    weight = c.d_model * c.linear_n_k_heads * c.linear_k_dim
    copied = [(op, dt, dims, name) for name, dt, dims, op, line
              in _entry_instructions(hlo)
              if op in ("copy", "slice", "dynamic-slice") and dims
              and "S(1)" not in line.split(" " + op + "(")[0]
              and dt == "bf16" and T not in map(int, dims.split(","))
              and np.prod(list(map(int, dims.split(",")))) >= weight]
    assert not copied, copied


def test_olmo_hybrid_step_returns_token_ids(one_chip, on_tpu):
    """The greedy choice is a result of the step's own program (PR 34):
    at the cell's decode shape the entry's tuple holds ``s32[64]`` beside
    the ``f32[64, 100352]`` logits, which a server never fetches (25.7 MB
    a tick). The logits are an operand now as well as a result, and the
    compiler answers by computing them into on-chip memory (``S(1)``) for
    the reduction and writing them out with an asynchronous
    ``copy-start`` / ``copy-done`` in place of the head fusion's own
    write: the same bytes to HBM once, no ``copy`` operation of that
    shape, and temporaries within 1 MB of what the step had without the
    ids (108,547,584 bytes by my described-chip compile of PR 31's tree,
    108,579,328 with them; 103,843,840 since PR 41, whose step kernel
    took the XLA step's reductions over the state leaf with it;
    43,991,040 since PR 54, which stopped the three attention layers'
    slices of ``full.wv`` being written out, 59.9 MB)."""
    compiled, c, e = compile_cell_step("olmo-hybrid-7b", one_chip, 64, 128)
    hlo = compiled.as_text()
    entry = hlo[hlo.index("\nENTRY "):]
    root = next(l for l in entry.splitlines() if l.lstrip().startswith("ROOT"))
    results = root[root.index("= (") + 3:root.index(") tuple(")]
    logits = f"f32[{e['max_seqs']},{c.vocab_size}]"
    assert results.startswith(logits + "{") and results.count(logits) == 1
    assert results.count(f"s32[{e['max_seqs']}]{{") == 1, results[:200]
    copies = [name for name, dt, dims, op, _ in _entry_instructions(hlo)
              if op == "copy" and (dt, dims) == ("f32", logits[4:-1])]
    assert not copies, copies
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert abs(temp - 43_991_040) < 1e6, temp


def _leaf_moves(hlo: str, leaf_dims: str) -> list:
    """Operations that copy, transpose, slice or relay a bf16 pool leaf of
    ``leaf_dims``, or hold one in another layout than the kernel's."""
    return [(i.op, n) for n, i in _instructions(hlo).items()
            if (i.dtype, i.dims) == ("bf16", leaf_dims)
            and (i.op in ("copy", "transpose", "slice", "dynamic-slice",
                          "gather", "concatenate", "copy-start")
                 or i.layout != "3,2,1,0")]


def _leaf_scatters(hlo: str, leaf_dims: str) -> list:
    """Scatters of the compiled text that yield a payload leaf of
    ``leaf_dims`` ([pages, heads, block, head_dim]), in that shape or seen
    as rows of ``head_dim``: what ``write_kv_rows`` compiles to."""
    pages, heads, block, hd = map(int, leaf_dims.split(","))
    shapes = (leaf_dims, f"{pages * heads * block},{hd}")
    return [l.strip()[:160] for l in hlo.splitlines()
            if re.search(r"= \w+\[(" + "|".join(shapes) + r")\]\S* scatter\(",
                         l)]


OURO_PAGES = 320   # what the chip's memory leaves of the file's cap of 512


def test_ouro_step_is_one_loop_and_copies_no_leaf(one_chip, on_tpu):
    """Rehearsal 3 for the cell ``ouro-2.6b.think``: the 48-layer step at
    64 lanes with four passes is ONE ``while`` whose body holds the 48
    blocks once (48 kernel calls in the text, 192 a tick), not four
    bodies; the loop carries the 96 pool leaves of ``4 x (pages + 1)``
    pages and nothing copies, transposes, slices or relays one: each is
    written in place by the row writer, one call a block for K and V, under
    the path ``attn/scatter`` (so ``attn_device_ms.serve`` counts the
    writes as in every other cell and ``loop_device_ms.serve`` reads
    them). It fits the chip beside 320
    pages (8.05 GB of pool, 5.34 GB of weights). Temporaries, stated:
    15.7 MB at 320 pages, the step's own activations (1.62 GB before the
    head split left the weight operand: PERF.md section 6, PR 54;
    ``test_served_step_reads_attention_stacks_in_place`` holds how the
    stacks are read); 18.6 MB at 160 pages, so they do not grow with the
    pool."""
    compiled, c, e = compile_cell_step("ouro-2.6b", one_chip, 64, 64,
                                       OURO_PAGES)
    assert c.total_ut_steps == 4 and c.n_layers == 48
    assert _device_bytes(compiled) < 15.75e9
    hlo = compiled.as_text()
    assert len(re.findall(r" while\(", hlo)) == 1
    assert _kernel_calls(hlo) == 48
    ins = _instructions(hlo)
    leaf = ("bf16", f"{4 * (OURO_PAGES + 1)},{c.n_kv_heads},"
                    f"{e['kv_block_size']},{c.head_dim}")
    leaves = {n: i for n, i in ins.items() if (i.dtype, i.dims) == leaf}
    assert len([i for i in leaves.values() if i.op == "parameter"]) == 96
    assert not _leaf_moves(hlo, leaf[1])
    # the rows are written by one call a block for both of its leaves, 48
    # in the loop's body (192 a tick where 384 scatter fusions were),
    # under the block's scope; no scatter of a leaf is left
    writes = _writer_calls(hlo)
    assert len(writes) == 48
    assert all("/while/body/closed_call/attn/scatter/" in w for w in writes)
    assert not _leaf_scatters(hlo, leaf[1])
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 32e6, temp
    smaller, _, _ = compile_cell_step("ouro-2.6b", one_chip, 64, 64,
                                      OURO_PAGES // 2)
    assert smaller.memory_analysis().temp_size_in_bytes < 32e6


def test_granite_hybrid_step_is_one_loop_and_copies_no_state_leaf(one_chip,
                                                                 on_tpu):
    """Rehearsal 3 for the cell ``granite-4.0-h-micro.assist``: the whole
    model's step at the decode shape is ONE rolled loop over the four
    periods of ten layers (unrolled, its 24 programs took 955 s of a cold
    warm-up on the chip: PERF.md section 6) and fits one chip beside the
    76 MB-a-sequence state pool and the 4,096 pages. One paged kernel call
    in the text, the attention layer of a period, with one call of the row
    writer in front of it under ``attn/scatter``, both in the loop's body:
    the pool lays the eight KV heads of 64 out as four rows of 128 lanes
    (``kv_cache.pool_leaves``), a leaf of the shape class of Mistral's, so
    the kernel takes the grid over query tiles and the engine holds a
    program a lane bucket (``_writes_pages``, ``_pages_key``). The pool has
    a leaf a layer of a period, the periods' runs end to end in it; each
    of the nine state leaves is written by one call of the state-space
    step kernel in the loop's body, which takes the leaf as an operand and
    returns it aliased, the period's first slot a prefetched scalar (where
    nine fusions over a period's whole run of 65 slots were, PR 44), and
    nothing copies, transposes, slices or selects a state leaf as an
    operation of its own, inside the loop or outside it (the chunk loop
    updates one slot in place, through a fused dynamic-update-slice). Nor
    is the K or the V leaf copied, transposed, scattered into or held in
    another layout than the kernel's, anywhere in the text: until PR 50 a
    ``bf16[16388,8,16,64]`` parameter (pages minor-most in the compiler's
    layout) was copied to the kernel's pinned row-major one before the loop
    and back after it, K and V, every tick (6.05 ms of a 27.6 ms step, two
    537 MB temporaries: PERF.md section 6)."""
    compiled, c, e = compile_cell_step("granite-4.0-h-micro", one_chip, 64,
                                       128)
    assert _device_bytes(compiled) < 15.75e9
    hlo = compiled.as_text()
    assert c.layer_period == 10 and len(c.layers_of("mamba")) == 36
    assert _kernel_calls(hlo) == 1
    page = f"{4 * (e['max_kv_blocks'] + 1)},4,{e['kv_block_size']},128"
    writes = _writer_calls(hlo)
    assert len(writes) == 1 and writes[0].count(f"bf16[{page}]") >= 2
    assert "/while/body/closed_call/attn/scatter/" in writes[0]
    # the grid over query tiles: the kernel's leaves are operands in HBM,
    # and the work list's 64 / 16 + 64 tiles are its grid
    paged, = [l for l in _custom_calls(hlo) if _KERNEL_RESULT.search(l)]
    assert "/while/body/closed_call/attn/paged_attention/" in paged
    assert paged.count(f"bf16[{page}]") == 2
    state = f"{4 * (e['max_seqs'] + 1)},64,64,128"
    # the nine Mamba layers of a period: one call of the step kernel each,
    # in the loop's body, on its own leaf at the period's run of slots
    steps = _step_calls(hlo)
    assert len(steps) == len(c.layers_of("mamba")) // 4 == 9
    assert all(f"f32[{state}]" in l
               and "/while/body/closed_call/ssm/ssd_step/" in l
               for l in steps)
    entry = list(_entry_instructions(hlo))
    assert sum(op == "parameter" and (dt, dims) == ("f32", state)
               for _, dt, dims, op, _ in entry) == 9
    assert sum(op == "parameter" and (dt, dims) == ("bf16", page)
               for _, dt, dims, op, _ in entry) == 2
    moved = re.findall(
        r"= f32\[" + state + r"\]\S* (copy|transpose|slice|dynamic-slice|"
        r"gather|concatenate|select)\(", hlo)
    assert not moved, moved
    assert not _leaf_moves(hlo, page)
    assert not _leaf_scatters(hlo, page)
    # ... nor is there an array of a leaf's size in the old shape, or in
    # any other with as many elements that is not the leaf
    assert f"{4 * (e['max_kv_blocks'] + 1)},8,{e['kv_block_size']},64" \
        not in hlo
    # temporaries: the step's own. 1,107,248,640 B with the K/V leaves'
    # second copies, padded to 128 lanes (my described-chip compile, PR
    # 44); 23,866,880 B without them (mine, PR 50)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9


# (config, lanes, live pages, pages of the pool or 0 for the file's, KV
# layers): the cells' decode steps at their files' depths. Mistral's
# token_budget shape is held at two layers of the same widths
# (test_ragged_step_compiles, test_ragged_step_writes_pool_in_place: a
# 16-layer compile at 2048 lanes is a minute of this file's one worker)
CELL_STEPS = {
    "mistral_decode": ("mistral-7b", 64, 128, 0, 16),
    "olmo_decode": ("olmo-hybrid-7b", 64, 128, 0, 3),
    "ouro_decode": ("ouro-2.6b", 64, 64, OURO_PAGES, 48),
}


def _cell_step(device_sharding, cell: str):
    """``compile_cell_step`` of a ``CELL_STEPS`` entry, called as the other
    tests of this file call it so that its cache answers."""
    config, T, live_pages, pages, _ = CELL_STEPS[cell]
    return compile_cell_step(config, device_sharding, T, live_pages,
                             *([pages] if pages else []))


@pytest.mark.parametrize("cell", sorted(CELL_STEPS))
def test_cell_step_writes_rows_by_one_call_a_layer(one_chip, on_tpu, cell):
    """On the TPU path no payload leaf of a serving cell is written by an
    XLA scatter: the compiled step holds one ``write_kv_pages`` call a KV
    layer (16 / 3 / 48 in the text; Ouro's 48 run four times a tick), each
    returning both leaves as a tuple under ``attn/scatter``, in front of
    that layer's paged kernel; and nothing copies, transposes, slices or
    relays a leaf."""
    config, T, live_pages, pages, kv_layers = CELL_STEPS[cell]
    compiled, c, e = _cell_step(one_chip, cell)
    hlo = compiled.as_text()
    assert _kernel_calls(hlo) == kv_layers
    assert len(_writer_calls(hlo)) == kv_layers
    # the delta-rule step kernel: a call a recurrent layer, and none in a
    # program without such layers (Mistral's, Ouro's)
    assert len(_step_calls(hlo)) == len(c.layers_of("linear")) \
        == (9 if config == "olmo-hybrid-7b" else 0)
    passes = c.total_ut_steps or 1
    leaf = (f"{passes * ((pages or e['max_kv_blocks']) + 1)},{c.n_kv_heads},"
            f"{e['kv_block_size']},{c.head_dim}")
    assert all(l.count(f"bf16[{leaf}]") >= 2 for l in _writer_calls(hlo))
    assert not _leaf_scatters(hlo, leaf)
    assert not _leaf_moves(hlo, leaf)
    if passes == 1:     # the loop's leaves come out of its carried tuple
        assert _pool_faults(hlo, heads=c.n_kv_heads,
                            leaf_pages=e["max_kv_blocks"] + 1) == []


@pytest.mark.parametrize("T,limit", [(64, 16e6), (2048, 80e6)],
                         ids=["decode", "prefill_chunk"])
def test_mistral_step_temporaries_are_its_activations(one_chip, on_tpu, T,
                                                      limit):
    """The 16-layer step holds no temporary of a weight's or the pool's
    size: 3.9 MB at 64 lanes and 76.0 MB at 2,048 (my described-chip
    compiles, PR 54; the larger program's follow the scheduler's
    prefetches, 64 to 81 MB over three constructions that read in place),
    the activations and the new rows laid out [hkv, T, hd] for the row
    writer. Until the head split of q / k / v left the weight operand
    they read 738.0 MB and 836.4 MB: every layer's slice of the stacked
    ``wq``, ``wk`` and ``wv`` leaves written out a tick
    (``Transformer._qkv``'s ``seam``; PERF.md section 6, PR 54)."""
    compiled, _, _ = compile_cell_step("mistral-7b", one_chip, T, 128)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < limit, temp


def test_roofline_readers_do_not_count_the_writer(one_chip, on_tpu):
    """``paged_attn_roofline_pct`` and ``loop_paged_attn_roofline_pct``
    know the paged kernel by its result's shape in an operation's name as
    ``trace_reduce.clean`` keeps it (``args.kernel`` of their metric
    files, which this repo's PRs may not edit). Over the cells' compiled
    steps that expression matches every paged-kernel call and no call of
    the row writer, whose result is a tuple: a writer that returned one
    bf16 leaf would be counted as attention."""
    import json

    from benchmarks import harness, trace_reduce as tr

    patterns = set()
    for metric in ("paged_attn_roofline_pct", "loop_paged_attn_roofline_pct"):
        spec = json.load(open(os.path.join(harness.HERE, "metrics",
                                           metric + ".json")))
        patterns.add(spec["args"]["kernel"])
    assert patterns
    for cell, (*_, kv_layers) in CELL_STEPS.items():
        compiled, _, _ = _cell_step(one_chip, cell)
        hlo = compiled.as_text()
        names = lambda lines: [tr.clean(l.strip().removeprefix("ROOT "))
                               for l in lines]
        writer = names(_writer_calls(hlo))
        paged = names(l for l in _custom_calls(hlo)
                      if _KERNEL_RESULT.search(l))
        assert len(writer) == len(paged) == kv_layers
        for pattern in map(re.compile, patterns):
            assert all(pattern.search(n) for n in paged), paged[:1]
            assert not any(pattern.search(n) for n in writer), writer[:1]


@pytest.mark.parametrize("T", [64, 256, 2048],
                         ids=["decode", "lanes256", "prefill_chunk"])
def test_mixtral_step_reads_expert_stacks_in_place(one_chip, on_tpu, T):
    """Rehearsal 3 for the cell ``mixtral-8x7b.chat``: no operation of the
    2-layer step yields a layer's expert matrices (8 x 4096 x 14336 bf16)
    or more. ``ragged_dot`` cannot fuse a ``w[li]`` into its operand read,
    so a sliced leaf is copied on every tick (three multi-output
    ``slice_bitcast_fusion``s, 5.6 GB written and read again: 17 of the
    tick's 28 ms of device time before PR 31, and 5.6 GB of the program's
    temporaries); the products index the whole stack by group instead
    (``no_drop_moe``'s ``layer``). Which product: the rule's
    (``parallel/moe.expert_product``). The decode program's 16 rows an
    expert and the 256-lane program's 64 are under the ridge: two
    ``grouped_matmul`` calls a layer in their tiled form (an expert's
    4096 x 14336 matrix is no single block) and no ``ragged_dot`` left,
    since PR 47, when an engine on the tiled
    attention path came to hold one step program a lane bucket and the
    kernels' lowering stopped costing the cell's set-up 28 programs'
    worth (PERF.md section 6). The 2,048-lane program's 512 rows an expert
    are past the ridge and keep ``ragged_dot``."""
    from deepspeed_tpu.parallel.moe import expert_product

    # the cell runs at JAX's default matmul precision; under conftest's
    # "highest" XLA:TPU's ragged_dot kernel refuses bf16 operands
    with jax.default_matmul_precision("default"):
        compiled, c, e = compile_cell_step("mixtral-8x7b", one_chip, T, 128)
    hlo = compiled.as_text()
    rows = T * c.top_k
    grouped = _grouped_calls(hlo)
    if T < 2048:
        assert expert_product("pallas", rows, c.n_experts) == "kernel"
        assert len(grouped) == 2 * c.n_layers
        assert {re.search(r"= bf16\[(\d+,\d+)\]", l).group(1)
                for l in grouped} == {f"{rows},{c.d_ff}",
                                      f"{rows},{c.d_model}"}
        assert "ragged-dot" not in hlo
    else:
        assert expert_product("pallas", rows, c.n_experts) == "ragged_dot"
        assert hlo.count("ragged-dot") >= 3 * c.n_layers
        assert not grouped
    leaf = c.n_experts * c.d_model * c.d_ff
    entry = hlo[hlo.index("\nENTRY "):]
    # results, tuples too (the parent's copies are multi-output fusions)
    made = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) (copy|slice|dynamic-slice"
                      r"|fusion)\(", re.M)
    copied = [(op, name) for name, results, op
              in made.findall(entry[:entry.index("\n}")])
              if any(np.prod(list(map(int, dims.split(",")))) >= leaf
                     for dims in re.findall(r"bf16\[([\d,]+)\]", results))]
    assert not copied, copied
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9
    assert _device_bytes(compiled) < 15.75 * 2 ** 30


@pytest.mark.parametrize("T", [256, 1024], ids=["lanes256", "lanes1024"])
def test_sdar_step_compiles_under_the_block_mask(one_chip, on_tpu, T):
    """Rehearsal 3 for the cell ``sdar-30b-a3b.answer``: the 7-layer step
    with 128 experts a layer, the paged kernel under the block-causal mask
    (``attn_block`` 4: Mosaic takes the ``|`` in the mask and in the trip
    count), the head on a block of lanes a slot and the choice under
    ``decide``; the expert stacks read in place by ``grouped_matmul``, two
    calls a layer (gate and up together, then down) where six
    ``ragged_dot`` kernels a layer stood and none is left (16 and 64 rows
    an expert: both programs are bound by the matrices' bytes), no
    operation yielding a layer's expert matrices, and the program beside
    its 9.97 GB of weights and 0.94 GB of pages inside the chip's
    memory."""
    import json

    from benchmarks import harness, trace_reduce as tr

    with jax.default_matmul_precision("default"):
        compiled, c, e = compile_cell_step("sdar-30b-a3b", one_chip, T, 64)
    hlo = compiled.as_text()
    assert (c.n_layers, c.n_experts, c.top_k, c.attn_block) == (7, 128, 8, 4)
    assert _kernel_calls(hlo) == c.n_layers
    assert len(_writer_calls(hlo)) == c.n_layers
    grouped = _grouped_calls(hlo)
    assert len(grouped) == 2 * c.n_layers
    assert {re.search(r"= bf16\[(\d+,\d+)\]", l).group(1) for l in grouped} \
        == {f"{T * c.top_k},{c.d_ff}", f"{T * c.top_k},{c.d_model}"}
    assert "ragged-dot" not in hlo
    # the paged kernel's roofline reader does not take them for attention
    spec = json.load(open(os.path.join(harness.HERE, "metrics",
                                       "paged_attn_roofline_pct.json")))
    paged = re.compile(spec["args"]["kernel"])
    assert not [l for l in grouped if paged.search(
        tr.clean(l.strip().removeprefix("ROOT ")))]
    # nothing yields a layer's expert matrices: the stacks are operands
    layer = {f"{c.n_experts},{c.d_model},{c.d_ff}",
             f"{c.n_experts},{c.d_ff},{c.d_model}"}
    assert not [i for i in _instructions(hlo).values() if i.dims in layer]
    assert "decide" in hlo
    mem = compiled.memory_analysis()
    assert 9.9e9 < mem.argument_size_in_bytes < 11.1e9
    assert mem.temp_size_in_bytes < 2.0e9
    assert _device_bytes(compiled) < 15.75 * 2 ** 30


def test_tp2_ragged_step_gathers_no_pool_leaf(topo, on_tpu):
    """Pool sharded over KV heads on two chips: GSPMD partitions the row
    write along its head index (an iota, so each chip writes its local
    heads) and the kernel runs in its shard_map: no collective and no copy
    moves a leaf, whole or per shard. Also the only place the
    tensor-parallel kernel path meets Mosaic's lowering, which wants
    every mesh axis manual."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.config import MeshConfig
    from deepspeed_tpu.parallel.mesh import Topology

    tp = Topology.build(MeshConfig(model=2), devices=topo.devices[:2])
    c = compile_ragged_step(NamedSharding(tp.mesh, P()), 2, 64, 128,
                            n_kv_blocks=POOL_PAGES, tp_topo=tp)
    assert _pool_faults(c.as_text(), heads=HKV // 2) == []


def _latent_calls(hlo: str) -> list:
    """The latent kernel's calls (``latent_attention``): each returns the
    lanes' [T, heads, latent] rows, three dimensions where the paged
    kernel's result has four, and runs under ``attn/latent``, the scope
    ``latent_attn_device_ms.serve`` reads."""
    calls = [l for l in _custom_calls(hlo)
             if re.search(r"= bf16\[\d+,\d+,\d+\]\{[\d,]+(:[^}]*)?\} custom-call", l)]
    assert all(re.search(r'op_name="[^"]*attn/latent/[^"]*"', l)
               for l in calls), calls
    return calls


@pytest.mark.parametrize("T", [64, 256, 1024],
                         ids=["lanes64", "lanes256", "lanes1024"])
def test_axk1_step_compiles_over_latent_pages(one_chip, on_tpu, T):
    """Rehearsal 3 for the cell ``a.x-k1.docs``: the 6-layer step at
    published widths: one ``latent_attention`` call a layer over the one
    latent leaf (64 heads over rows of 640, Mosaic takes the lane-indexed
    q block and the two branches), its rows written by the scatter (one
    index a lane) with no copy of a leaf; the expert share's products on
    the grouped kernel under the ridge (12 held experts: 64 and 256
    lanes) and ``ragged_dot`` past it, no operation yielding a layer's
    expert matrices; the program beside 8.33 GB of weights and the 4.03
    GB pool inside the chip's memory."""
    from deepspeed_tpu.parallel.moe import expert_product

    with jax.default_matmul_precision("default"):
        compiled, c, e = compile_cell_step("a.x-k1", one_chip, T, 1024)
    hlo = compiled.as_text()
    assert (c.n_layers, c.n_experts, c.n_held, c.top_k) == (6, 192, 12, 8)
    assert len(_latent_calls(hlo)) == c.n_layers
    grouped = _grouped_calls(hlo)
    n_moe = c.n_layers - c.first_dense_layers
    if expert_product("pallas", T * c.top_k, c.n_held) == "kernel":
        assert len(grouped) == 2 * n_moe and "ragged-dot" not in hlo
    else:
        assert not grouped and hlo.count("ragged-dot") >= 3 * n_moe
    # nothing yields a layer's expert matrices or a whole latent leaf
    leaf = f"{e['max_kv_blocks'] + 1},1,{e['kv_block_size']},{c.latent_row}"
    assert not _leaf_moves(hlo, leaf), _leaf_moves(hlo, leaf)
    layer = {f"{c.n_held},{c.d_model},{c.d_ff}",
             f"{c.n_held},{c.d_ff},{c.d_model}"}
    assert not [i for i in _instructions(hlo).values() if i.dims in layer]
    mem = compiled.memory_analysis()
    assert 12.3e9 < mem.argument_size_in_bytes < 12.5e9
    assert mem.temp_size_in_bytes < 1.5e9
    assert _device_bytes(compiled) < 15.75 * 2 ** 30


# ----------------------------------------------------------------------
# the served step's q / k / v products read the stacked leaves in place
# config -> (lanes, live pages, pages of the pool or 0 for the file's): a
# cached decode step of each serving configuration whose attention layers'
# leaves come out of a stack by a static slice (Granite's rolled loop
# slices dynamically and copies no matrix)
ATTENTION_STACK_STEPS = {
    "mistral-7b": (64, 128, 0),
    "mixtral-8x7b": (64, 128, 0),
    "olmo-hybrid-7b": (64, 128, 0),
    "ouro-2.6b": (64, 64, OURO_PAGES),
    "sdar-30b-a3b": (256, 64, 0),
    "a.x-k1": (64, 1024, 0),
}
_HEAD_SPLIT_LEAF = re.compile(r"^params__layers__(?:__full__)?__"
                              r"(?:w[qkv]|w_uq)__")


def _computations(hlo: str) -> dict:
    """name -> lines of each computation of a compiled module's text."""
    out, name = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%(\S+) \(.*\) -> .* \{$", line)
        if m:
            name = m.group(1)
            out[name] = []
        elif name is not None:
            out[name].append(line)
    return out


def _stack_readers(hlo: str) -> list:
    """(what, name) of every operation that runs by itself (in the entry
    computation or a loop's body, not inside a fusion) and takes a stacked
    ``wq`` / ``wk`` / ``wv`` / ``w_uq`` leaf as an operand: a fusion by its
    kind, anything else by its opcode. In a loop's body, where a leaf is an
    element of the carried tuple, a leaf is known by its shape (``wo``
    shares ``wq``'s in Mistral and Ouro, and is read in place as well)."""
    comps = _computations(hlo)
    fused = set(re.findall(r" fusion\(.*?calls=%([\w.\-]+)", hlo))
    shapes, readers = set(), []
    for comp, lines in comps.items():     # the entry's parameters, by name
        for n, i in _instructions("\n".join(lines)).items():
            if i.op == "parameter" and _HEAD_SPLIT_LEAF.match(n):
                shapes.add((i.dtype, i.dims))
    assert shapes
    for comp, lines in comps.items():
        if comp in fused:
            continue
        ins = _instructions("\n".join(lines))
        leaves = {n for n, i in ins.items()
                  if (i.dtype, i.dims) in shapes
                  and (_HEAD_SPLIT_LEAF.match(n) if i.op == "parameter"
                       else i.op == "get-tuple-element")}
        for line in lines:
            m = re.match(r"^\s*(?:ROOT )?%(\S+) = (.*)$", line)
            call = m and re.search(r"\s([a-z][\w\-]*)\((%[^)]*)\)", m.group(2))
            if not call or not leaves & set(
                    re.findall(r"%([\w.\-]+)", call.group(2))):
                continue
            kind = re.search(r"kind=(\w+)", line)
            readers.append((kind.group(1) if call.group(1) == "fusion"
                            else call.group(1), m.group(1)))
    return readers


@pytest.mark.parametrize("config", sorted(ATTENTION_STACK_STEPS))
def test_served_step_reads_attention_stacks_in_place(one_chip, on_tpu,
                                                     config):
    """No tick copies a stacked attention weight: in each serving
    configuration's decode step every operation that takes ``wq``, ``wk``,
    ``wv`` (``w_uq`` under latent attention) as an operand is an output
    fusion, the product itself with the layer's slice fused into its
    operand read, or the compiler's own prefetch of pieces into VMEM
    (``slice-start`` / ``copy-start``, asynchronous, in the leaf's
    layout), or the loop that carries the leaf (Ouro's). Before
    ``Transformer._qkv``'s ``seam`` XLA folded the head split of a
    product's result onto its weight operand,
    and a slice under that bitcast does not fuse: a ``kLoop``
    ``slice_bitcast_fusion`` a leaf wrote every layer's slice out on every
    tick (Mistral 768 MB, Mixtral's ``wq`` / ``wk``, Olmo-Hybrid's
    ``full.wv``, SDAR's ``wq``, A.X-K1's ``w_uq`` 226 MB), and Ouro's
    step copied the three stacks whole to another layout before its loop
    (``copy``, 1.21 GB) and sliced the copies inside it (PERF.md section
    6, PR 54)."""
    T, live_pages, pages = ATTENTION_STACK_STEPS[config]
    with jax.default_matmul_precision("default"):   # as the cells run
        compiled, c, _ = compile_cell_step(config, one_chip, T, live_pages,
                                           *([pages] if pages else []))
    hlo = compiled.as_text()
    readers = _stack_readers(hlo)
    in_place = {"kOutput", "slice-start", "copy-start", "tuple", "while"}
    assert readers and not [r for r in readers if r[0] not in in_place], \
        readers
    # the products are there: a layer's three (w_uq's one) read the leaf
    # itself, or pieces of it that the compiler's prefetch brought
    products = [r for r in readers if r[0] == "kOutput"]
    prefetched = {r[0] for r in readers} & {"slice-start", "copy-start"}
    assert products if prefetched else len(products) >= len(
        c.layers_of("full")) * (1 if c.kv_lora_rank else 3), readers
