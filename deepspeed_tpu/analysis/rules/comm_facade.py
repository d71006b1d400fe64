"""comm-facade: raw ``jax.lax`` collectives in ZeRO-3 hot paths.

The compressed-collectives facade (``comm/compressed.py``,
docs/communication.md) is the shipped large-mesh ZeRO-3 communication
path: every collective it issues is metered in the bytes-on-wire ledger,
carries the compression policy (quantize the slow hop, stay dense on
fast ICI), and degrades cleanly when a tensor can't block-divide. A raw
``jax.lax.psum`` / ``all_gather`` / ``psum_scatter`` / ``all_to_all`` /
``ppermute`` dropped straight into ``parallel/zero.py`` or
``runtime/engine.py`` bypasses all three — the wire volume disappears
from the evidence ledger, the compression threshold silently stops
applying, and the T3 overlap schedule can't stage what it can't see.

Scope (path-based, like the wall-clock rule): files named
``parallel/zero*.py`` or ``runtime/engine*.py`` — the ZeRO placement /
schedule layer and the training engine. The facade
module itself and the low-level collective layers (``comm/comm.py``,
``comm/compressed.py``, ``parallel/compressed.py``, ``parallel/ring.py``,
...) are out of scope: they ARE the implementation the facade wraps.

One check:

* ``raw-collective`` — a call that resolves to a ``jax.lax`` collective
  (``jax.lax.X(...)``, ``lax.X(...)`` via an import alias, or a
  from-imported ``X(...)``). Route it through ``deepspeed_tpu.comm``
  (the thin wrappers) or ``deepspeed_tpu.comm.compressed`` (the
  quantized/hierarchical paths).

Deliberate raw sites take the usual suppression-with-reason.
"""

from __future__ import annotations

import ast
import re
from typing import Iterator, List, Optional

from ..findings import Finding
from ..model import FunctionInfo, ModuleInfo, PackageModel, iter_shallow
from ..registry import Rule, register

#: ZeRO-3 hot-path modules whose collectives must flow through the facade
_SCOPE = re.compile(r"(^|/)(parallel/zero[^/]*\.py|runtime/engine[^/]*\.py)$")

#: jax.lax collective primitives (the wire-moving set)
_COLLECTIVES = {"psum", "pmean", "pmax", "pmin", "all_gather",
                "psum_scatter", "reduce_scatter", "all_to_all", "ppermute"}


def _attr_chain(node: ast.AST) -> Optional[List[str]]:
    """['jax', 'lax', 'psum'] for jax.lax.psum — None for anything that
    isn't a plain dotted name."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return list(reversed(parts))
    return None


def _resolves_to_lax(mod: ModuleInfo, func: ast.AST) -> Optional[str]:
    """The collective name when ``func`` resolves to jax.lax.<collective>,
    else None. Handles ``jax.lax.X``, ``import jax.lax as lax`` /
    ``from jax import lax`` + ``lax.X``, and ``from jax.lax import X``."""
    if isinstance(func, ast.Name):
        imp = mod.name_imports.get(func.id)
        if imp and imp[0].lstrip(".") == "jax.lax" and imp[1] in _COLLECTIVES:
            return imp[1]
        return None
    chain = _attr_chain(func)
    if not chain or len(chain) < 2:
        return None
    name = chain[-1]
    if name not in _COLLECTIVES:
        return None
    head = chain[0]
    base = mod.alias_to_module.get(head)
    if base is None:
        imp = mod.name_imports.get(head)
        if imp:
            base = imp[0].lstrip(".") + "." + imp[1]
    if base is None:
        return None
    full = ".".join([base] + chain[1:-1])
    return name if full == "jax.lax" else None


@register
class CommFacadeRule(Rule):
    id = "comm-facade"
    summary = ("raw jax.lax collectives in ZeRO-3 hot paths "
               "(parallel/zero*.py, runtime/engine*.py) that bypass the "
               "compressed-collectives facade and its wire ledger")

    def run(self, pkg: PackageModel) -> Iterator[Finding]:
        for mod in pkg.modules.values():
            if not _SCOPE.search(mod.key):
                continue
            for f in pkg.functions_in(mod.key):
                yield from self._check(f, mod)

    def _check(self, f: FunctionInfo, mod: ModuleInfo) -> Iterator[Finding]:
        for node in iter_shallow(f.node):
            if not isinstance(node, ast.Call):
                continue
            name = _resolves_to_lax(mod, node.func)
            if name is None:
                continue
            yield Finding(
                rule=self.id, code="raw-collective", path=mod.key,
                line=node.lineno, col=node.col_offset,
                symbol=f.qualname,
                message=f"raw jax.lax.{name} in a ZeRO-3 hot path bypasses "
                        f"the compressed-collectives facade — route it "
                        f"through deepspeed_tpu.comm (thin wrappers) or "
                        f"comm.compressed (quantized/hierarchical paths) so "
                        f"the bytes-on-wire ledger and compression policy "
                        f"see it (docs/communication.md)")
