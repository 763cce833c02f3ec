"""Shared device-path annotation vocabulary for dlint.

The device rules and the P_DLINT tripwire agree on a tiny comment grammar —
the same "declare intent where the code is" pattern plint uses for lock
hierarchies and wlint uses for wire headers:

``# jit-cache: <family>[.<program>]``
    On a module-level dict assignment: declares a memoized program cache
    (family).  On a call-time ``jax.jit(...)`` line (or its enclosing def
    line): declares which cache the built program flows through, and names
    the program for tripwire attribution / the ``tpu_recompiles_total``
    metric label.

``# sync-boundary[: reason]``
    Marks a line (or a whole function, via its def line) as a *declared*
    device->host synchronization point — a priced readback.  The host-sync
    rule exempts declared boundaries; everything else reachable from a hot
    loop is a finding.

``# device-hot``
    Marks a loop/function as a device hot path.  These are the roots the
    host-sync rule walks the call graph from; no root, no reachability.

``# link-priced[: reason]``
    Marks a ``device_put``/``device_get`` (or the function owning it) as
    accounted for in route_stats byte accounting even though
    the pricing calls live elsewhere in the function.

Annotations are read from ``SourceFile.comments`` (tokenize-derived, so
they work on the same line as code).  A line-level annotation may sit on
the flagged line itself or on the line directly above it — multi-line
calls make same-line comments awkward.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field

from parseable_tpu.analysis.framework import SourceFile

JIT_CACHE_RE = re.compile(r"jit-cache:\s*([A-Za-z_][A-Za-z0-9_.-]*)")
SYNC_BOUNDARY_RE = re.compile(r"sync-boundary\b")
DEVICE_HOT_RE = re.compile(r"device-hot\b")
LINK_PRICED_RE = re.compile(r"link-priced\b")

#: Files that constitute "the device layer" for path-scoped rules.  The
#: analysis package itself is excluded upstream (the analyzer does not lint
#: itself); tests are excluded because tests touch device arrays on purpose.
DEVICE_MODULE_PREFIXES = (
    "parseable_tpu/ops/",
    "parseable_tpu/parallel/",
)
DEVICE_MODULE_FILES = (
    "parseable_tpu/query/executor_tpu.py",
    "parseable_tpu/query/sketch.py",
)

#: Attribute reads that are static under tracing — touching them does NOT
#: propagate device/traced taint (``x.shape[0]`` is a Python int).
STATIC_ATTRS = frozenset(
    {"shape", "ndim", "dtype", "size", "itemsize", "nbytes", "sharding",
     "aval", "weak_type", "at"}
)


def is_device_module(rel: str) -> bool:
    if rel in DEVICE_MODULE_FILES:
        return True
    return rel.startswith(DEVICE_MODULE_PREFIXES) and rel.endswith(".py")


@dataclass
class DeviceAnnotations:
    """Per-file index of dlint annotations, keyed by line number."""

    jit_cache: dict[int, str] = field(default_factory=dict)
    sync_boundary: set[int] = field(default_factory=set)
    device_hot: set[int] = field(default_factory=set)
    link_priced: set[int] = field(default_factory=set)

    def jit_cache_at(self, *lines: int) -> str | None:
        """First jit-cache annotation on any of the given lines."""
        for ln in lines:
            name = self.jit_cache.get(ln)
            if name:
                return name
        return None

    def _near(self, index: set[int], node: ast.AST, fn: ast.AST | None) -> bool:
        lines = {node.lineno, node.lineno - 1}
        if fn is not None and hasattr(fn, "lineno"):
            lines |= {fn.lineno, fn.lineno - 1}
        return bool(lines & index)

    def sync_boundary_near(self, node: ast.AST, fn: ast.AST | None = None) -> bool:
        return self._near(self.sync_boundary, node, fn)

    def link_priced_near(self, node: ast.AST, fn: ast.AST | None = None) -> bool:
        return self._near(self.link_priced, node, fn)


def annotations_for(sf: SourceFile) -> DeviceAnnotations:
    """Extract (and memoize on the SourceFile) this file's annotations."""
    cached = getattr(sf, "_device_annotations", None)
    if cached is not None:
        return cached
    ann = DeviceAnnotations()
    for line, text in sf.comments.items():
        m = JIT_CACHE_RE.search(text)
        if m:
            ann.jit_cache[line] = m.group(1)
        if SYNC_BOUNDARY_RE.search(text):
            ann.sync_boundary.add(line)
        if DEVICE_HOT_RE.search(text):
            ann.device_hot.add(line)
        if LINK_PRICED_RE.search(text):
            ann.link_priced.add(line)
    sf._device_annotations = ann
    return ann
