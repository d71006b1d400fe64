"""dslint fixture: PLANTED trace-hygiene violations."""
import time

import jax
import numpy as np

from deepspeed_tpu.profiling.trace import annotate

_CALLS = 0


class Layer:
    def apply(self, registry, xs):
        def body(carry, x):
            global _CALLS                   # PLANT: global-stmt
            t = time.time()                 # PLANT: wall-clock
            n = np.random.randn()           # PLANT: np-random
            self.calls = 1                  # PLANT: attr-mutation
            registry.counter("steps").inc()  # PLANT: telemetry-call (.inc)
            return carry + x + t + n, x

        return jax.lax.scan(body, 0.0, xs)

    def traced_step(self, tracer, flight, xs):
        def body(carry, x):
            tracer.event(None, "tick")       # PLANT: tracer-call (event)
            flight.note("step", x=1)         # PLANT: tracer-call (note)
            with tracer.span("block"):       # PLANT: tracer-call (span)
                carry = carry + x
            with annotate("block", lanes=1):  # PLANT: tracer-call (annotate)
                carry = carry + x
            return carry, x

        return jax.lax.scan(body, 0.0, xs)
