"""A serving tick's host-to-device transfer, alone on the chip: the five
int32 arrays of ``RaggedInferenceEngine._launch`` sent as five
``jnp.asarray``, as one ``jax.device_put`` of the tuple, and packed into one
buffer sent once (the form ``_launch`` has since PR 51). Each form is timed
twice: the host's time in the transfer alone, and a whole round (transfer,
a jitted consumer that reads every field, the result fetched), which is
what tells a cost that went from one that moved behind the call.

    python scripts/h2d_pack_bench.py [--lanes 64] [--seqs 64] [--pages 256]

Chip only (a CPU run times a memcpy). One JSON line a form to stdout and to
``chiprun_out/h2d_pack_bench.jsonl``.
"""
import argparse
import json
import os
import statistics
import sys
import time

import numpy as np


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lanes", type=int, default=64)
    ap.add_argument("--seqs", type=int, default=64)
    ap.add_argument("--pages", type=int, default=256)
    ap.add_argument("--rounds", type=int, default=2000)
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from deepspeed_tpu.inference.ragged import pack_fields

    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    host = tuple(rng.integers(0, 1000, s).astype(np.int32) for s in (
        (a.lanes,), (a.lanes,), (a.lanes,), (a.seqs, a.pages), (a.seqs,)))
    n_bytes = sum(x.nbytes for x in host)

    five = jax.jit(lambda *xs: sum(x.sum() for x in xs))

    @jax.jit
    def one(packed):
        t, s, p = a.lanes, a.seqs, a.pages
        parts = [packed[i * t:(i + 1) * t] for i in range(3)]
        parts.append(packed[3 * t:3 * t + s * p].reshape(s, p))
        parts.append(packed[3 * t + s * p:])
        return sum(x.sum() for x in parts)

    forms = {
        "five_asarray": (lambda: [jnp.asarray(x) for x in host],
                         lambda sent: five(*sent)),
        "tuple_device_put": (lambda: jax.device_put(host),
                             lambda sent: five(*sent)),
        "packed_asarray": (lambda: jnp.asarray(pack_fields(host)), one),
        "packed_device_put": (lambda: jax.device_put(pack_fields(host)), one),
        "pack_only": (lambda: pack_fields(host), None),
    }
    want = int(sum(int(x.sum()) for x in host))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/h2d_pack_bench.jsonl", "a") as out:
        for name, (send, consume) in forms.items():
            if consume is not None:
                assert int(consume(send())) == want, name
            for _ in range(50):                       # warm every path
                sent = send()
                if consume is not None:
                    np.asarray(consume(sent))
            put, whole = [], []
            for _ in range(a.rounds):
                t0 = time.perf_counter()
                sent = send()
                t1 = time.perf_counter()
                if consume is not None:
                    np.asarray(consume(sent))
                t2 = time.perf_counter()
                put.append(t1 - t0)
                whole.append(t2 - t0)
            q = lambda xs: [round(v * 1e3, 4) for v in
                            statistics.quantiles(xs, n=4)]
            line = {"form": name, "device": dev.device_kind,
                    "platform": dev.platform, "bytes": n_bytes,
                    "lanes": a.lanes, "seqs": a.seqs, "pages": a.pages,
                    "rounds": a.rounds, "put_ms_quartiles": q(put),
                    "round_ms_quartiles": q(whole) if consume else None}
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
