"""dslint fixture: near-miss TRUE NEGATIVES for trace-hygiene."""
import time

import jax
import jax.numpy as jnp

from deepspeed_tpu.profiling.trace import annotate


class Layer:
    def apply(self, registry, xs, key):
        t0 = time.time()            # host side: timing around the trace
        self.calls = 1              # host-side attribute bookkeeping

        def body(carry, x):
            local = {}              # local container mutation is fine
            local["noise"] = jax.random.normal(key)   # jax RNG: traced
            return carry + x + local["noise"], x

        out = jax.lax.scan(body, 0.0, xs)
        registry.counter("steps").inc()   # telemetry on the host: fine
        return out, time.time() - t0

    def host_traced_step(self, tracer, flight, xs):
        # tracer spans / flight-recorder appends AROUND the traced call,
        # on the host: exactly the contract the rule enforces
        with tracer.span("step"), annotate("step", lanes=1):
            def body(carry, x):
                with jax.named_scope("attn"):   # device names: metadata
                    return carry + jnp.tanh(x), x

            out = jax.lax.scan(body, 0.0, xs)
        tracer.event(None, "step_done")
        flight.note("step_done")
        return out
