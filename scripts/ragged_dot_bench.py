"""The experts' grouped product alone, on the chip: ``jax.lax.ragged_dot``
beside ``ops/pallas/grouped_matmul.py`` (and, as a first reading, megablox's
``gmm``) at the two shapes the serving cells hold, both products, by rows.

    chiprun -- python scripts/ragged_dot_bench.py [--shapes sdar,mixtral]
        [--rows 512,2048,8192] [--groups even,multinomial] [--megablox 1]

Each product runs as the serving step runs it: over the whole stored stack
[L * E, K, N] with one layer's group sizes, every layer of the stack in one
jitted program (a tick's worth), timed by the host's clock around
``block_until_ready`` and divided by the layers. One JSON line a
(shape, rows, groups) to stdout and to
``chiprun_out/ragged_dot_bench.jsonl``: ms a product and GB/s of the
matrices of the experts touched. PERF.md section 6 (PR 46) has the table
and the rule that follows it (``parallel/moe.expert_product``). Fails off the TPU.
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from deepspeed_tpu.ops.pallas import grouped_matmul as gm  # noqa: E402

#: name -> (experts, K, N, layers in the cell's stack)
SHAPES = {"sdar": (128, 2048, 768, 7), "mixtral": (8, 4096, 14336, 2)}
ROWS = {"sdar": (512, 2048, 8192), "mixtral": (128, 512, 2048, 4096)}


def timed(f, *a, n=10):
    out = f(*a)
    jax.block_until_ready(out)
    t = time.perf_counter()
    for _ in range(n):
        out = f(*a)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n


def sizes_of(how, rows, E, seed):
    if how == "even":
        g = np.full((E,), rows // E, np.int32)
        g[: rows - g.sum()] += 1
        return g
    rng = np.random.default_rng(seed)
    return np.bincount(rng.integers(0, E, rows), minlength=E).astype(np.int32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="sdar,mixtral")
    ap.add_argument("--rows", default="")
    ap.add_argument("--groups", default="even,multinomial")
    ap.add_argument("--megablox", type=int, default=0)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"ragged_dot_bench measures the chip; found {dev.platform}")
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/ragged_dot_bench.jsonl", "a")
    key = jax.random.PRNGKey(0)
    for shape in args.shapes.split(","):
        E, K, N, L = SHAPES[shape]
        draw = lambda k, s: jax.random.normal(k, s, jnp.bfloat16) * 0.02
        k1, k2, k3 = jax.random.split(key, 3)
        w_up, w_gate, w_down = (draw(k1, (L * E, K, N)),
                                draw(k2, (L * E, K, N)),
                                draw(k3, (L * E, N, K)))
        layers = range(L)

        def padded(g, li):
            return jnp.pad(g, (li * E, (L - 1 - li) * E))

        # a tick's worth: every layer's product in one program
        ragged = jax.jit(lambda x, w, g: [
            jax.lax.ragged_dot(x, w, padded(g, li)) for li in layers])
        # the layer's schedule inside the timed program, as the step
        # builds it (there once a layer, for both of its calls)
        sched = lambda x, g, li: gm.visits(g, x.shape[0], li * E)
        kernel = jax.jit(lambda x, w, g: [
            gm.grouped_matmul(x, w, sched(x, g, li)) for li in layers])
        ragged_glu = jax.jit(lambda x, wg, wu, g: [
            jax.nn.silu(jax.lax.ragged_dot(x, wg, padded(g, li)))
            * jax.lax.ragged_dot(x, wu, padded(g, li)) for li in layers])
        kernel_glu = jax.jit(lambda x, wg, wu, g: [
            gm.grouped_matmul(x, wg, sched(x, g, li), wu) for li in layers])
        rows_list = [int(r) for r in args.rows.split(",")] if args.rows \
            else ROWS[shape]
        for rows in rows_list:
            x = jax.random.normal(key, (rows, K), jnp.float32
                                  ).astype(jnp.bfloat16)
            h = jax.random.normal(key, (rows, N), jnp.float32
                                  ).astype(jnp.bfloat16)
            for how in args.groups.split(","):
                g_np = sizes_of(how, rows, E, rows)
                g = jnp.asarray(g_np)
                touched = int((g_np > 0).sum())
                byt = touched * K * N * 2
                line = {"device": dev.device_kind, "shape": shape,
                        "E": E, "K": K, "N": N, "layers": L, "rows": rows,
                        "rows_per_expert": rows / E, "groups": how,
                        "experts_touched": touched,
                        "tiles": {"tm": gm.tile_rows(rows),
                                  "up": gm.weight_tiles(K, N, jnp.bfloat16),
                                  "down": gm.weight_tiles(N, K,
                                                          jnp.bfloat16)}}
                # the same answers first: the kernel against ragged_dot
                a = ragged(x, w_up, g)[L - 1].astype(jnp.float32)
                b = kernel(x, w_up, g)[L - 1].astype(jnp.float32)
                line["up_max_abs_diff"] = float(jnp.max(jnp.abs(a - b)))
                line["up_ref_max_abs"] = float(jnp.max(jnp.abs(a)))
                for name, f, fk, ops in (
                        ("up", ragged, kernel, (x, w_up, g)),
                        ("down", ragged, kernel, (h, w_down, g)),
                        ("glu", ragged_glu, kernel_glu,
                         (x, w_gate, w_up, g))):
                    n_w = 2 if name == "glu" else 1
                    for who, fn in (("ragged_dot", f), ("kernel", fk)):
                        t = timed(fn, *ops) / L
                        line[f"{name}_{who}_ms"] = round(t * 1e3, 4)
                        line[f"{name}_{who}_GBps"] = round(
                            n_w * byt / t / 1e9, 1)
                if args.megablox:
                    from jax.experimental.pallas.ops.tpu.megablox.gmm import \
                        gmm

                    one = w_up[:E]
                    mb = jax.jit(lambda x, w, g: gmm(
                        x, w, g, preferred_element_type=jnp.bfloat16,
                        tiling=(gm.tile_rows(rows),)
                        + gm.weight_tiles(K, N, jnp.bfloat16)))
                    t = timed(mb, x, one, g)
                    line["up_megablox_ms"] = round(t * 1e3, 4)
                    line["up_megablox_GBps"] = round(byt / t / 1e9, 1)
                out = json.dumps(line)
                print(out, flush=True)
                sink.write(out + "\n")
                sink.flush()


if __name__ == "__main__":
    main()
