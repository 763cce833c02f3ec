"""dlint rules for device->host synchronization and transfer discipline.

* ``host-sync`` — a reachability rule over the PR 5 call graph.  Roots are
  functions containing a ``# device-hot`` annotation (the executor's block
  dispatch loops); from there the rule walks direct (non-deferred,
  non-executor) call edges, exactly like plint's ``blocking_reach``, and
  flags synchronizing constructs in any reachable device-layer function:
  ``.block_until_ready()`` and ``.item()`` on anything, and
  ``np.asarray``/``np.array``/``float()``/``int()``/``bool()`` on values the
  intraprocedural taint pass knows are device arrays.  A declared
  ``# sync-boundary: <why>`` (line or whole function) is exempt — the point
  is not "never sync" but "every sync is declared and priced".
* ``transfer-discipline`` — every ``jax.device_put``/``device_get`` in the
  query path must be priced into the byte accounting
  (``DEVICE_BYTES_TO_DEVICE`` / the ``h2d_bytes``/``d2h_bytes`` route
  counters) within its enclosing named function, or carry a
  ``# link-priced: <where>`` annotation pointing at the accounting.
  Lambdas are opaque: a ship inside a lambda needs the line annotation.
"""

from __future__ import annotations

import ast

from parseable_tpu.analysis.callgraph import build_call_graph
from parseable_tpu.analysis.framework import (
    Finding,
    Project,
    Rule,
    SourceFile,
    attr_chain,
    enclosing_context,
)

from .annotations import STATIC_ATTRS, annotations_for, is_device_module

#: Attribute-chain roots whose call results live on device.
_DEVICE_ROOTS = ("jnp",)
#: Cache variables whose ``.get()`` yields a compiled device program.
_PROGRAM_HINTS = ("program", "cache", "prog")

_PRICING_NAMES = frozenset({"DEVICE_BYTES_TO_DEVICE"})
_PRICING_KEYS = frozenset({"h2d_bytes", "d2h_bytes"})


def _is_device_put_get(node: ast.AST) -> str | None:
    if not isinstance(node, ast.Call):
        return None
    ch = attr_chain(node.func)
    if ch[-1:] == ["device_put"] or ch == ["jax", "device_get"]:
        return ch[-1]
    return None


# ----------------------------------------------------- host-sync taint pass


def _own_nodes(fn: ast.AST):
    """Nodes of `fn`'s body excluding nested def/class bodies (lambdas are
    transparent — their body executes in this frame's dynamic extent)."""
    stack = list(getattr(fn, "body", []))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _targets(node: ast.AST) -> list[str]:
    if isinstance(node, ast.Name):
        return [node.id]
    if isinstance(node, (ast.Tuple, ast.List)):
        out: list[str] = []
        for el in node.elts:
            out.extend(_targets(el))
        return out
    if isinstance(node, ast.Starred):
        return _targets(node.value)
    return []


class _DeviceTaint:
    """Which local names hold device arrays / compiled device programs."""

    def __init__(self, fn: ast.AST) -> None:
        self.values: set[str] = set()
        self.callables: set[str] = set()
        changed = True
        while changed:
            changed = False
            for node in _own_nodes(fn):
                value = None
                targets: list[str] = []
                if isinstance(node, ast.Assign):
                    value = node.value
                    for t in node.targets:
                        targets.extend(_targets(t))
                elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                    value = node.value
                    targets.extend(_targets(node.target))
                elif isinstance(node, ast.For):
                    value = node.iter
                    targets.extend(_targets(node.target))
                elif isinstance(node, ast.NamedExpr):
                    value = node.value
                    targets.extend(_targets(node.target))
                if value is None or not targets:
                    continue
                if self._is_device_callable_source(value):
                    fresh = set(targets) - self.callables
                    if fresh:
                        self.callables |= fresh
                        changed = True
                elif self.is_device(value):
                    fresh = set(targets) - self.values
                    if fresh:
                        self.values |= fresh
                        changed = True

    def _is_device_callable_source(self, node: ast.AST) -> bool:
        if not isinstance(node, ast.Call):
            return False
        ch = attr_chain(node.func)
        if ch in (["jax", "jit"], ["jit"]):
            return True
        if ch[-1:] == ["get"] and len(ch) >= 2 and any(
            h in ch[-2].lower() for h in _PROGRAM_HINTS
        ):
            return True
        tail = ch[-1] if ch else ""
        return bool(tail) and "program" in tail.lower()

    def is_device(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.values
        if isinstance(node, ast.Attribute):
            if node.attr in STATIC_ATTRS:
                return False
            return self.is_device(node.value)
        if isinstance(node, ast.Subscript):
            return self.is_device(node.value)
        if isinstance(node, ast.Lambda):
            return False
        if isinstance(node, ast.Call):
            ch = attr_chain(node.func)
            if ch:
                if ch[0] in _DEVICE_ROOTS:
                    return True
                if ch == ["jax", "device_put"]:
                    return True
                if ch[-1] == "trace":
                    return True  # PredicateCompiler.trace -> device mask
                if ch[0] in self.callables and len(ch) == 1:
                    return True
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr not in STATIC_ATTRS:
                # method on a device value returns a device value (x.sum())
                return self.is_device(f.value)
            return False
        return any(self.is_device(c) for c in ast.iter_child_nodes(node))


class HostSyncRule(Rule):
    """Undeclared device->host syncs reachable from hot loops.

    Every sync on the hot path must either go away or become a declared,
    priced boundary (``# sync-boundary: <why>``): the executor's
    ``_timed_readback`` ticks ``d2h_bytes`` and the phase clock, so an
    undeclared ``np.asarray`` is both a stall *and* invisible to the byte
    accounting.
    """

    name = "host-sync"
    description = "undeclared device->host sync reachable from a # device-hot root"
    rationale = (
        "an implicit sync serializes dispatch against device completion "
        "and bypasses the byte accounting; declared boundaries "
        "(_timed_readback) are the only allowed syncs"
    )

    def applies(self, rel: str) -> bool:
        return False  # all work happens in finalize (needs the call graph)

    def finalize(self, project: Project):
        graph = build_call_graph(project)
        by_rel = {sf.rel: sf for sf in project.files}

        # roots: innermost functions containing a `# device-hot` line
        roots: list[str] = []
        for key, fi in graph.funcs.items():
            if not is_device_module(fi.rel) or fi.node is None:
                continue
            sf = by_rel.get(fi.rel)
            if sf is None:
                continue
            ann = annotations_for(sf)
            end = getattr(fi.node, "end_lineno", fi.line)
            for hot in ann.device_hot:
                if fi.line <= hot <= end:
                    inner = max(
                        (
                            g
                            for g in graph.funcs.values()
                            if g.rel == fi.rel
                            and g.node is not None
                            and g.line <= hot <= getattr(g.node, "end_lineno", g.line)
                        ),
                        key=lambda g: g.line,
                        default=fi,
                    )
                    if inner.key == key:
                        roots.append(key)
                    break

        reached: dict[str, tuple[str, ...]] = {r: (r,) for r in roots}
        queue = list(roots)
        while queue:
            k = queue.pop(0)
            fi = graph.funcs.get(k)
            if fi is None:
                continue
            for e in sorted(fi.edges, key=lambda e: e.line):
                if e.deferred or e.executor:
                    continue
                if e.callee in graph.funcs and e.callee not in reached:
                    reached[e.callee] = reached[k] + (e.callee,)
                    queue.append(e.callee)

        for key, chain in reached.items():
            fi = graph.funcs[key]
            if not is_device_module(fi.rel) or fi.node is None:
                continue
            sf = by_rel.get(fi.rel)
            if sf is None:
                continue
            ann = annotations_for(sf)
            taint = _DeviceTaint(fi.node)
            via = " -> ".join(
                graph.funcs[k].qualname for k in chain if k in graph.funcs
            )
            for node in _own_nodes(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                label = self._sync_label(node, taint)
                if label is None:
                    continue
                if ann.sync_boundary_near(node, fi.node):
                    continue
                yield Finding(
                    rule=self.name,
                    path=fi.rel,
                    line=node.lineno,
                    message=(
                        f"undeclared device->host sync ({label}) on the hot "
                        f"path (device-hot root via {via}) — route through a "
                        "priced readback or declare `# sync-boundary: <why>`"
                    ),
                    context=fi.qualname,
                )

    @staticmethod
    def _sync_label(node: ast.Call, taint: _DeviceTaint) -> str | None:
        ch = attr_chain(node.func)
        tail = ch[-1] if ch else (
            node.func.attr if isinstance(node.func, ast.Attribute) else ""
        )
        if tail == "block_until_ready":
            return ".block_until_ready()"
        if tail == "item" and not node.args:
            return ".item()"
        if ch[-1:] in (["asarray"], ["array"]) and len(ch) == 2 and ch[0] in (
            "np",
            "numpy",
        ):
            if node.args and taint.is_device(node.args[0]):
                return f"np.{ch[-1]} on a device array"
            return None
        if (
            isinstance(node.func, ast.Name)
            and node.func.id in ("float", "int", "bool")
            and len(node.args) == 1
            and taint.is_device(node.args[0])
        ):
            return f"{node.func.id}() on a device array"
        return None


class TransferDisciplineRule(Rule):
    """Unpriced device_put/device_get in the query path.

    Per-query host<->device traffic is the budget the device path is
    designed around, and ``h2d_bytes``/``d2h_bytes`` are how a response
    (and the benchmark) sees it.  A ship that bypasses the counters is
    traffic nobody can read.
    """

    name = "transfer-discipline"
    description = "device_put/device_get must be priced into byte accounting"
    rationale = (
        "unpriced transfers are missing from the h2d/d2h bytes a response "
        "reports; a data-sized ship inside a loop is the expensive variant "
        "of the same bug"
    )

    def applies(self, rel: str) -> bool:
        return rel.startswith(("parseable_tpu/query/", "parseable_tpu/ops/")) and (
            rel.endswith(".py")
        )

    def check(self, sf: SourceFile):
        if sf.tree is None:
            return
        ann = annotations_for(sf)

        sites: list[tuple[ast.Call, str, ast.AST | None, bool, bool]] = []

        def visit(node: ast.AST, fn: ast.AST | None, in_lambda: bool, in_loop: bool):
            for child in ast.iter_child_nodes(node):
                nfn, nlam, nloop = fn, in_lambda, in_loop
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    nfn, nlam, nloop = child, False, False
                elif isinstance(child, ast.Lambda):
                    nlam = True
                elif isinstance(child, (ast.For, ast.AsyncFor, ast.While)):
                    nloop = True
                kind = _is_device_put_get(child)
                if kind:
                    sites.append((child, kind, nfn, nlam, nloop))
                visit(child, nfn, nlam, nloop)

        visit(sf.tree, None, False, False)

        for call, kind, fn, in_lambda, in_loop in sites:
            if ann.link_priced_near(call, None if in_lambda else fn):
                continue
            if ann.sync_boundary_near(call, None if in_lambda else fn):
                continue
            if fn is not None and not in_lambda and self._priced(fn):
                continue
            where = " inside a lambda" if in_lambda else ""
            loop = " inside a loop" if in_loop else ""
            yield Finding(
                rule=self.name,
                path=sf.rel,
                line=call.lineno,
                message=(
                    f"jax.{kind}{where}{loop} is not priced into route_stats "
                    "byte accounting — tick DEVICE_BYTES_TO_DEVICE or "
                    "the h2d_bytes/d2h_bytes route counters, or annotate "
                    "`# link-priced: <where the bytes are tallied>`"
                ),
                context=enclosing_context(sf.tree, call),
            )

    @staticmethod
    def _priced(fn: ast.AST) -> bool:
        for n in ast.walk(fn):
            if isinstance(n, ast.Call):
                ch = attr_chain(n.func)
                if ch and ch[-1] in _PRICING_NAMES:
                    return True
            elif isinstance(n, ast.Name) and n.id in _PRICING_NAMES:
                return True
            elif isinstance(n, ast.Constant) and n.value in _PRICING_KEYS:
                return True
        return False
