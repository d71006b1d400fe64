"""trace-hygiene: host state touched from inside traced code.

A traced function body runs ONCE, at trace time — not once per step.
``time.time()`` reads the clock during tracing and bakes a constant
into the program; ``np.random`` draws a single sample forever;
mutating ``self``/globals from a traced body aliases trace-time state
into runtime expectations; and a telemetry call inside a jitted body
breaks PR 2's zero-sync-when-off contract (telemetry must observe the
*host* side of the step, never live inside the program).

Scope: the traced set only (same as host-sync).
"""

from __future__ import annotations

import ast
from typing import Iterator, Optional

from ..findings import Finding
from ..model import (PackageModel, FunctionInfo, ModuleInfo,
                     final_attr_name, dotted_name, iter_shallow)
from ..registry import Rule, register

_TIME_FUNCS = {"time", "perf_counter", "monotonic", "process_time",
               "sleep", "perf_counter_ns", "time_ns"}
_TELEMETRY_CALLS = {"record_step", "record_request",
                    "record_request_span", "log_dist", "get_telemetry"}
_REGISTRY_FACTORIES = {"counter", "histogram", "gauge"}
_REGISTRY_OPS = {"inc", "observe"}
# request-tracer entry points (telemetry/tracing.py): spans/events and
# flight-recorder appends observe the HOST side of a step — inside a
# jitted body they would fire once at trace time and (worse) read the
# clock seam into a compiled constant. Distinctive names match any call
# shape; the generic ones (span/event/note) only as METHOD calls
# (tracer.span(...), flight.note(...)) so an unrelated local helper
# named `note` inside traced code is not hijacked.
_TRACER_CALLS = {"new_trace", "begin_span", "finish_span",
                 "span_complete", "get_tracer", "note_span"}
_TRACER_METHOD_CALLS = {"span", "event", "note"}


def _module_of(mod: ModuleInfo, func: ast.AST) -> Optional[str]:
    """Real dotted module a call like ``alias.attr(...)`` targets, or the
    source module of a from-imported name."""
    if isinstance(func, ast.Attribute):
        dn = dotted_name(func)
        if dn is None:
            return None
        head = dn.split(".")[0]
        real = mod.alias_to_module.get(head)
        if real is None:
            return None
        rest = dn[len(head):].rsplit(".", 1)[0]
        return real + rest if rest else real
    if isinstance(func, ast.Name):
        imp = mod.name_imports.get(func.id)
        if imp:
            return imp[0].lstrip(".")
    return None


@register
class TraceHygieneRule(Rule):
    id = "trace-hygiene"
    summary = ("wall clocks, host RNG, global/attribute mutation and "
               "telemetry calls inside traced code")

    def run(self, pkg: PackageModel) -> Iterator[Finding]:
        for f in pkg.functions.values():
            if f.traced_reason is None:
                continue
            yield from self._check(f, pkg.modules[f.module])

    def _check(self, f: FunctionInfo,
               mod: ModuleInfo) -> Iterator[Finding]:
        why = f" [traced: {f.traced_reason}]"
        for node in iter_shallow(f.node):
            if isinstance(node, ast.Global):
                yield Finding(
                    rule=self.id, code="global-stmt", path=mod.key,
                    line=node.lineno, col=node.col_offset,
                    symbol=f.qualname,
                    message="`global` inside traced code mutates host "
                            f"state at trace time, not per step{why}")
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    base = t
                    while isinstance(base, ast.Subscript):
                        base = base.value
                    if isinstance(base, ast.Attribute):
                        yield Finding(
                            rule=self.id, code="attr-mutation",
                            path=mod.key, line=node.lineno,
                            col=node.col_offset, symbol=f.qualname,
                            message=f"assignment to "
                                    f"`{dotted_name(base) or '<attr>'}` "
                                    f"inside traced code runs at trace "
                                    f"time only — return the value "
                                    f"through the carry instead{why}")
                        break
            elif isinstance(node, ast.Call):
                yield from self._check_call(node, f, mod, why)

    def _check_call(self, node: ast.Call, f: FunctionInfo,
                    mod: ModuleInfo, why: str) -> Iterator[Finding]:
        name = final_attr_name(node.func)
        src_mod = _module_of(mod, node.func)
        if src_mod == "time" and name in _TIME_FUNCS:
            yield Finding(
                rule=self.id, code="wall-clock", path=mod.key,
                line=node.lineno, col=node.col_offset, symbol=f.qualname,
                message=f"time.{name}() in traced code is evaluated "
                        f"once at trace time — time on the host, around "
                        f"the step call{why}")
        elif src_mod is not None and (
                src_mod == "numpy.random"
                or src_mod.startswith("numpy.random")):
            yield Finding(
                rule=self.id, code="np-random", path=mod.key,
                line=node.lineno, col=node.col_offset, symbol=f.qualname,
                message=f"np.random.{name}() in traced code draws ONE "
                        f"sample at trace time — thread a jax.random "
                        f"key through the carry{why}")
        elif src_mod == "random":
            yield Finding(
                rule=self.id, code="py-random", path=mod.key,
                line=node.lineno, col=node.col_offset, symbol=f.qualname,
                message=f"random.{name}() in traced code is a "
                        f"trace-time constant — use jax.random{why}")
        elif src_mod in {"datetime", "datetime.datetime"} \
                and name in {"now", "utcnow", "today"}:
            yield Finding(
                rule=self.id, code="wall-clock", path=mod.key,
                line=node.lineno, col=node.col_offset, symbol=f.qualname,
                message=f"datetime {name}() in traced code is a "
                        f"trace-time constant{why}")
        elif name in _TELEMETRY_CALLS:
            yield Finding(
                rule=self.id, code="telemetry-call", path=mod.key,
                line=node.lineno, col=node.col_offset, symbol=f.qualname,
                message=f"{name}() inside traced code breaks the "
                        f"zero-sync-when-off contract — record on the "
                        f"host after the step returns{why}")
        elif name in _TRACER_CALLS or (
                isinstance(node.func, ast.Attribute)
                and name in _TRACER_METHOD_CALLS):
            yield Finding(
                rule=self.id, code="tracer-call", path=mod.key,
                line=node.lineno, col=node.col_offset, symbol=f.qualname,
                message=f"{name}() (request tracer / flight recorder) "
                        f"inside traced code would fire once at trace "
                        f"time with a trace-time clock stamp — span on "
                        f"the host, around the step call{why}")
        elif name == "annotate" and (src_mod or "").endswith(
                "profiling.trace"):
            # the one path to the profiler's host track (PR 25): a host
            # span in a traced body would cover the trace, not the step
            yield Finding(
                rule=self.id, code="tracer-call", path=mod.key,
                line=node.lineno, col=node.col_offset, symbol=f.qualname,
                message=f"annotate() (profiler host span) inside traced "
                        f"code spans the trace, not the step — annotate "
                        f"on the host around the call and name device "
                        f"work with jax.named_scope{why}")
        elif isinstance(node.func, ast.Attribute) \
                and name in _REGISTRY_OPS:
            # x.inc(...) / x.observe(...): registry series mutation
            yield Finding(
                rule=self.id, code="telemetry-call", path=mod.key,
                line=node.lineno, col=node.col_offset, symbol=f.qualname,
                message=f".{name}() (metrics registry) inside traced "
                        f"code — metrics must be host-side{why}")
        elif (isinstance(node.func, ast.Attribute)
                and name in _REGISTRY_FACTORIES
                and isinstance(node.func.value, (ast.Name, ast.Attribute))
                and (final_attr_name(node.func.value) or "").lower()
                    .endswith(("registry", "telemetry"))):
            yield Finding(
                rule=self.id, code="telemetry-call", path=mod.key,
                line=node.lineno, col=node.col_offset, symbol=f.qualname,
                message=f"registry.{name}() inside traced code — "
                        f"metrics must be host-side{why}")
