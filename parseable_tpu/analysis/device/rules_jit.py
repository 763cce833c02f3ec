"""dlint rules for jit compilation discipline.

Four rules share one per-file index (`_index`): the set of call-time
``jax.jit(...)`` sites, the declared program caches, and the *traced
bodies* — functions whose Python source executes under a JAX trace,
discovered from ``@jax.jit``/``@partial(jax.jit, ...)`` decorators and by
resolving call-time ``jax.jit(name)`` through enclosing-scope local defs
and simple aliases (``body = shard_map(fold, ...)``).

* ``jit-cache-discipline`` — a call-time jit on a query path must carry a
  ``# jit-cache: <family>.<program>`` annotation naming a declared
  module-level cache, and the enclosing function must actually read from
  and store into that cache.  Otherwise every call recompiles.
* ``traced-control-flow`` — Python ``if``/``while``/``assert`` on a traced
  value inside a jit'd body: a silent per-branch recompile at best, a
  ConcretizationTypeError at worst.  Taint starts at the traced params
  (minus static_argnums) and flows through assignments; ``.shape`` and
  friends break taint.
* ``dtype-promotion`` — float64 references inside traced bodies (and
  ``jax_enable_x64`` flips anywhere in the device layer).  The kernels are
  f32; a single f64 leak doubles HBM traffic and recompiles everything.
* ``donation-hazard`` — reading a Python name after it was passed at a
  ``donate_argnums`` position is a use-after-donate error; call-time jit
  *without* donation is an advisory unless a nearby comment documents the
  no-donate rationale (see the executor.dense note in executor_tpu).
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field

from parseable_tpu.analysis.framework import (
    Finding,
    Project,
    Rule,
    SourceFile,
    attr_chain,
    enclosing_context,
)

from .annotations import STATIC_ATTRS, annotations_for, is_device_module

_JIT_CHAINS = (["jax", "jit"], ["jit"])
_SHARD_MAP_TAILS = ("shard_map",)

#: Calls whose result is static under tracing even with traced arguments.
_STATIC_CALLS = frozenset({"len", "range"})


def _is_jit_call(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) and attr_chain(node.func) in _JIT_CHAINS


def _int_positions(node: ast.AST) -> set[int]:
    """Literal int / tuple-of-int positions from a static_argnums value."""
    out: set[int] = set()
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        out.add(node.value)
    elif isinstance(node, (ast.Tuple, ast.List)):
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, int):
                out.add(el.value)
    return out


def _str_names(node: ast.AST) -> set[str]:
    out: set[str] = set()
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        out.add(node.value)
    elif isinstance(node, (ast.Tuple, ast.List)):
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                out.add(el.value)
    return out


def _static_from_keywords(call: ast.Call) -> tuple[set[int], set[str]]:
    nums: set[int] = set()
    names: set[str] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            nums |= _int_positions(kw.value)
        elif kw.arg == "static_argnames":
            names |= _str_names(kw.value)
    return nums, names


def _param_names(fn: ast.FunctionDef | ast.AsyncFunctionDef) -> list[str]:
    a = fn.args
    return [p.arg for p in (*a.posonlyargs, *a.args)]


def _static_param_names(
    fn: ast.FunctionDef | ast.AsyncFunctionDef, nums: set[int], names: set[str]
) -> set[str]:
    params = _param_names(fn)
    out = set(names)
    for i in nums:
        if 0 <= i < len(params):
            out.add(params[i])
    return out


def _own_statements(fn: ast.AST):
    """Every node in `fn`'s body, not descending into nested def/class
    bodies (lambdas are transparent)."""
    body = getattr(fn, "body", [])
    stack = list(body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            continue
        stack.extend(ast.iter_child_nodes(node))


@dataclass
class _TracedBody:
    fn: ast.FunctionDef | ast.AsyncFunctionDef
    static_names: set[str]
    origin_line: int
    via: str


@dataclass
class _FileIndex:
    jit_sites: list[tuple[ast.Call, tuple]] = field(default_factory=list)
    module_jit: list[ast.Call] = field(default_factory=list)
    cache_decls: dict[str, tuple[str, int]] = field(default_factory=dict)
    traced: list[_TracedBody] = field(default_factory=list)


def _local_defs(fn: ast.AST) -> dict[str, ast.AST]:
    """Directly visible defs + simple aliases within one scope's own
    statements: ``name = other``, ``name = shard_map(f, ...)``."""
    out: dict[str, ast.AST] = {}
    aliases: dict[str, ast.AST] = {}
    for node in _own_statements(fn):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out[node.name] = node
        elif isinstance(node, ast.Assign) and len(node.targets) == 1 and isinstance(
            node.targets[0], ast.Name
        ):
            aliases[node.targets[0].id] = node.value
    # resolve one-step aliases against the defs we saw
    for name, value in aliases.items():
        target = value
        if isinstance(target, ast.Call) and attr_chain(target.func)[-1:] == list(
            _SHARD_MAP_TAILS
        ):
            target = target.args[0] if target.args else None
        if isinstance(target, ast.Name):
            out.setdefault(name, ast.Name(id=target.id))
        elif isinstance(target, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.setdefault(name, target)
    return out


def _resolve_callable(name: str, scopes: list[dict]) -> ast.AST | None:
    """Innermost-out resolution of `name` to a def node, following Name
    aliases a bounded number of hops."""
    for _ in range(5):
        found = None
        for scope in reversed(scopes):
            if name in scope:
                found = scope[name]
                break
        if found is None:
            return None
        if isinstance(found, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return found
        if isinstance(found, ast.Name):
            name = found.id
            continue
        return None
    return None


def _index(sf: SourceFile) -> _FileIndex:
    cached = getattr(sf, "_dlint_jit_index", None)
    if cached is not None:
        return cached
    idx = _FileIndex()
    tree = sf.tree
    if tree is None:
        sf._dlint_jit_index = idx
        return idx
    ann = annotations_for(sf)

    # calls appearing inside decorator expressions are not call-time sites
    deco_calls: set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                for c in ast.walk(dec):
                    if isinstance(c, ast.Call):
                        deco_calls.add(id(c))

    # declared program caches: module-level assigns annotated `# jit-cache: fam`
    for stmt in tree.body:
        target = None
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
            target = stmt.targets[0]
        elif isinstance(stmt, ast.AnnAssign):
            target = stmt.target
        if isinstance(target, ast.Name):
            fam = ann.jit_cache_at(stmt.lineno, stmt.lineno - 1)
            if fam:
                idx.cache_decls[fam.split(".")[0]] = (target.id, stmt.lineno)

    def visit(node: ast.AST, stack: tuple) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                visit(child, stack + (child,))
                continue
            if (
                isinstance(child, ast.Call)
                and _is_jit_call(child)
                and id(child) not in deco_calls
            ):
                if stack:
                    idx.jit_sites.append((child, stack))
                else:
                    idx.module_jit.append(child)
            visit(child, stack)

    visit(tree, ())

    # traced bodies from decorators
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for dec in node.decorator_list:
            nums: set[int] = set()
            names: set[str] = set()
            traced = False
            if attr_chain(dec) in _JIT_CHAINS:
                traced = True
            elif isinstance(dec, ast.Call):
                ch = attr_chain(dec.func)
                if ch in _JIT_CHAINS:
                    traced = True
                    nums, names = _static_from_keywords(dec)
                elif ch[-1:] == ["partial"] and dec.args and attr_chain(
                    dec.args[0]
                ) in _JIT_CHAINS:
                    traced = True
                    nums, names = _static_from_keywords(dec)
            if traced:
                idx.traced.append(
                    _TracedBody(
                        node,
                        _static_param_names(node, nums, names),
                        node.lineno,
                        f"@jit decorator at line {node.lineno}",
                    )
                )
                break

    # traced bodies from call-time and module-level jit sites
    module_scope = _local_defs(tree)
    for call, stack in [*[(c, ()) for c in idx.module_jit], *idx.jit_sites]:
        if not call.args:
            continue
        arg0 = call.args[0]
        target = arg0
        if isinstance(target, ast.Call) and attr_chain(target.func)[-1:] == list(
            _SHARD_MAP_TAILS
        ):
            target = target.args[0] if target.args else None
        fn = None
        if isinstance(target, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fn = target
        elif isinstance(target, ast.Name):
            scopes = [module_scope] + [_local_defs(s) for s in stack]
            fn = _resolve_callable(target.id, scopes)
        if fn is None:
            continue
        nums, names = _static_from_keywords(call)
        idx.traced.append(
            _TracedBody(
                fn,
                _static_param_names(fn, nums, names),
                call.lineno,
                f"jax.jit at line {call.lineno}",
            )
        )

    sf._dlint_jit_index = idx
    return idx


# ------------------------------------------------------------ taint engine


def _expr_tainted(node: ast.AST, tainted: set[str]) -> bool:
    if isinstance(node, ast.Name):
        return node.id in tainted
    if isinstance(node, ast.Compare) and all(
        isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops
    ):
        # `x is (not) None` is a host-level structural check: the None-ness
        # of a name is static even when the value it may hold is traced
        return False
    if isinstance(node, ast.Attribute):
        if node.attr in STATIC_ATTRS:
            return False
        return _expr_tainted(node.value, tainted)
    if isinstance(node, ast.Lambda):
        return False
    if isinstance(node, ast.Call):
        ch = attr_chain(node.func)
        if ch and ch[-1] in _STATIC_CALLS:
            return False
        if any(_expr_tainted(a, tainted) for a in node.args):
            return True
        if any(kw.value is not None and _expr_tainted(kw.value, tainted)
               for kw in node.keywords):
            return True
        if isinstance(node.func, ast.Attribute):
            if node.func.attr in STATIC_ATTRS:
                return False
            return _expr_tainted(node.func.value, tainted)
        return False
    return any(_expr_tainted(c, tainted) for c in ast.iter_child_nodes(node))


def _target_names(node: ast.AST) -> list[str]:
    if isinstance(node, ast.Name):
        return [node.id]
    if isinstance(node, (ast.Tuple, ast.List)):
        out: list[str] = []
        for el in node.elts:
            out.extend(_target_names(el))
        return out
    if isinstance(node, ast.Starred):
        return _target_names(node.value)
    return []


def _flag_traced_body(
    sf: SourceFile,
    body: _TracedBody,
    tainted: set[str],
    out: list[Finding],
    seen: set[tuple],
    visited: set[tuple],
    depth: int = 0,
) -> None:
    key = (id(body.fn), frozenset(tainted))
    if key in visited or depth > 3:
        return
    visited.add(key)

    # fixpoint taint propagation over own statements (loops feed backwards)
    changed = True
    while changed:
        changed = False
        for node in _own_statements(body.fn):
            targets: list[str] = []
            value = None
            if isinstance(node, ast.Assign):
                value = node.value
                for t in node.targets:
                    targets.extend(_target_names(t))
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                value = node.value
                targets.extend(_target_names(node.target))
            elif isinstance(node, ast.For):
                value = node.iter
                targets.extend(_target_names(node.target))
            elif isinstance(node, ast.NamedExpr):
                value = node.value
                targets.extend(_target_names(node.target))
            if value is not None and targets and _expr_tainted(value, tainted):
                fresh = set(targets) - tainted
                if fresh:
                    tainted |= fresh
                    changed = True

    for node in _own_statements(body.fn):
        kw = None
        if isinstance(node, ast.If):
            kw = "if"
        elif isinstance(node, ast.While):
            kw = "while"
        elif isinstance(node, ast.Assert):
            kw = "assert"
        if kw is None or not _expr_tainted(node.test, tainted):
            continue
        mark = (node.lineno, kw)
        if mark in seen:
            continue
        seen.add(mark)
        out.append(
            Finding(
                rule="traced-control-flow",
                path=sf.rel,
                line=node.lineno,
                message=(
                    f"Python `{kw}` on a traced value inside jit'd body "
                    f"`{body.fn.name}` ({body.via}) — this concretizes the "
                    "tracer (recompile per branch at best); use jnp.where/"
                    "lax.cond/lax.while_loop or hoist to a static argument"
                ),
                context=enclosing_context(sf.tree, node) or body.fn.name,
            )
        )

    # propagate into directly nested defs: by tainted call-argument position,
    # or wholesale when the def is handed to a combinator (fori_loop, scan…)
    nested = {
        n.name: n
        for n in ast.iter_child_nodes(body.fn)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    }
    for name, fn in nested.items():
        sub = _TracedBody(fn, set(), body.origin_line, body.via)
        params = _param_names(fn)
        closure = {t for t in tainted if t not in params}
        handed_off = False
        for node in _own_statements(body.fn):
            if not isinstance(node, ast.Call):
                continue
            direct = isinstance(node.func, ast.Name) and node.func.id == name
            if direct:
                pos_taint = {
                    params[i]
                    for i, a in enumerate(node.args)
                    if i < len(params) and _expr_tainted(a, tainted)
                }
                if pos_taint or closure:
                    _flag_traced_body(
                        sf, sub, pos_taint | closure, out, seen, visited, depth + 1
                    )
            elif any(
                isinstance(a, ast.Name) and a.id == name for a in node.args
            ):
                handed_off = True
        if handed_off:
            _flag_traced_body(
                sf, sub, set(params) | closure, out, seen, visited, depth + 1
            )


# ------------------------------------------------------------------- rules


class JitCacheDisciplineRule(Rule):
    """Call-time ``jax.jit`` must flow through a declared program cache.

    A ``jax.jit(closure)`` executed per query builds (and on a TPU backend,
    compiles) a fresh program every call — the recompile-per-query failure
    mode the paper's static-plan reference architecture never has.  The
    discipline: annotate the site ``# jit-cache: <family>.<program>``,
    declare the cache at module level (``_CACHE = {}  # jit-cache:
    <family>``), and make the enclosing function read from and store into
    it, keyed by shape/dtype/static-args.  The P_DLINT tripwire then
    attributes every real XLA compile to the declared program name.
    """

    name = "jit-cache-discipline"
    description = "call-time jax.jit must ride a declared, keyed program cache"
    rationale = (
        "an unkeyed call-time jit recompiles per query; the 3 executor "
        "program families exist precisely to amortize tracing+XLA compile "
        "across warm queries"
    )

    def applies(self, rel: str) -> bool:
        return rel.startswith(("parseable_tpu/query/", "parseable_tpu/ops/")) and (
            rel.endswith(".py")
        )

    def check(self, sf: SourceFile):
        idx = _index(sf)
        ann = annotations_for(sf)
        for call, stack in idx.jit_sites:
            fn = stack[-1]
            cache_name = ann.jit_cache_at(
                call.lineno, call.lineno - 1, fn.lineno, fn.lineno - 1
            )
            ctx = enclosing_context(sf.tree, call)
            if cache_name is None:
                yield Finding(
                    rule=self.name,
                    path=sf.rel,
                    line=call.lineno,
                    message=(
                        "call-time jax.jit() builds a program on every "
                        "invocation — annotate `# jit-cache: "
                        "<family>.<program>` and route it through a keyed "
                        "program cache"
                    ),
                    context=ctx,
                )
                continue
            family = cache_name.split(".")[0]
            decl = idx.cache_decls.get(family)
            if decl is None:
                yield Finding(
                    rule=self.name,
                    path=sf.rel,
                    line=call.lineno,
                    message=(
                        f"`# jit-cache: {cache_name}` names cache family "
                        f"'{family}' but no module-level declaration "
                        f"(`CACHE = {{}}  # jit-cache: {family}`) exists"
                    ),
                    context=ctx,
                )
                continue
            var = decl[0]
            has_lookup = has_store = False
            for n in ast.walk(fn):
                if isinstance(n, ast.Call):
                    ch = attr_chain(n.func)
                    if ch == [var, "get"]:
                        has_lookup = True
                elif isinstance(n, ast.Subscript) and isinstance(
                    n.value, ast.Name
                ) and n.value.id == var:
                    if isinstance(n.ctx, ast.Store):
                        has_store = True
                    else:
                        has_lookup = True
                elif isinstance(n, ast.Compare) and any(
                    isinstance(c, ast.Name) and c.id == var
                    for c in n.comparators
                ):
                    has_lookup = True
            if not (has_lookup and has_store):
                missing = "read from" if not has_lookup else "stored into"
                yield Finding(
                    rule=self.name,
                    path=sf.rel,
                    line=call.lineno,
                    message=(
                        f"jit'd program '{cache_name}' is never {missing} "
                        f"cache '{var}' in this function — it is rebuilt on "
                        "every call despite the annotation"
                    ),
                    context=ctx,
                )


class TracedControlFlowRule(Rule):
    """Python control flow on traced values inside jit'd bodies.

    ``if``/``while``/``assert`` on a tracer either concretizes (error) or
    burns a recompile per branch taken.  Traced bodies are discovered from
    decorators and from call-time jit sites resolved through local defs and
    ``shard_map`` aliases; static_argnums/static_argnames params are exempt,
    and ``.shape``/``.dtype``-style static reads break the taint.
    """

    name = "traced-control-flow"
    description = "Python if/while/assert on traced values in jit'd bodies"
    rationale = (
        "branching on a tracer is a ConcretizationTypeError at worst and a "
        "silent per-branch recompile at best; lax.cond/jnp.where keep the "
        "program static"
    )

    def applies(self, rel: str) -> bool:
        return is_device_module(rel)

    def check(self, sf: SourceFile):
        idx = _index(sf)
        out: list[Finding] = []
        seen: set[tuple] = set()
        visited: set[tuple] = set()
        for body in idx.traced:
            tainted = set(_param_names(body.fn)) - body.static_names
            _flag_traced_body(sf, body, tainted, out, seen, visited)
        return out


class DtypePromotionRule(Rule):
    """float64 leaking into the f32 device layer.

    The kernels, accumulators, and wire formats are float32 end to end
    (README "dtype discipline"); a float64 reference inside a traced body
    doubles HBM traffic and recompiles every downstream program, and
    ``jax_enable_x64`` flips the default for the whole process.
    """

    name = "dtype-promotion"
    description = "float64 references inside traced bodies / x64 enable flips"
    rationale = (
        "one f64 leak silently promotes the whole lattice: 2x HBM, new "
        "program shapes, and a recompile storm the tripwire would attribute "
        "to every cache family at once"
    )

    def applies(self, rel: str) -> bool:
        return is_device_module(rel)

    def check(self, sf: SourceFile):
        idx = _index(sf)
        seen: set[int] = set()
        for body in idx.traced:
            for node in ast.walk(body.fn):
                hit = None
                if isinstance(node, ast.Attribute) and node.attr == "float64":
                    hit = "float64 reference"
                elif isinstance(node, ast.Constant) and node.value == "float64":
                    hit = 'dtype string "float64"'
                if hit and node.lineno not in seen:
                    seen.add(node.lineno)
                    yield Finding(
                        rule=self.name,
                        path=sf.rel,
                        line=node.lineno,
                        message=(
                            f"{hit} inside jit'd body `{body.fn.name}` — the "
                            "device layer is f32; promote on the host after "
                            "readback instead"
                        ),
                        context=enclosing_context(sf.tree, node),
                    )
        if sf.tree is not None:
            for node in ast.walk(sf.tree):
                if (
                    isinstance(node, ast.Call)
                    and attr_chain(node.func)[-2:] == ["config", "update"]
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and node.args[0].value == "jax_enable_x64"
                    and not (
                        len(node.args) > 1
                        and isinstance(node.args[1], ast.Constant)
                        and node.args[1].value is False
                    )
                ):
                    yield Finding(
                        rule=self.name,
                        path=sf.rel,
                        line=node.lineno,
                        message=(
                            "jax_enable_x64 flipped in the device layer — "
                            "this promotes every weak-typed literal in every "
                            "kernel to f64 process-wide"
                        ),
                        context=enclosing_context(sf.tree, node),
                    )


class DonationHazardRule(Rule):
    """Buffer-donation misuse at call-time jit sites.

    Reading a name after it was passed at a ``donate_argnums`` position is
    a use-after-donate (the buffer is gone).  The inverse — a call-time jit
    with *no* donation — is only an advisory, and only when no nearby
    comment documents why (executor_tpu's executor.dense note says what
    the no-donate choice rests on).
    """

    name = "donation-hazard"
    description = "use-after-donate errors; undocumented missed donation (advisory)"
    rationale = (
        "a donated buffer is deallocated on dispatch: any later host read "
        "is undefined; whether donation pays is backend-dependent and not "
        "measured on a directly attached chip, so absence is advisory-only"
    )

    def applies(self, rel: str) -> bool:
        return rel.startswith(("parseable_tpu/query/", "parseable_tpu/ops/")) and (
            rel.endswith(".py")
        )

    def check(self, sf: SourceFile):
        idx = _index(sf)
        for call, stack in idx.jit_sites:
            donate: set[int] = set()
            for kw in call.keywords:
                if kw.arg == "donate_argnums":
                    donate |= _int_positions(kw.value)
            if not donate:
                continue
            fn = stack[-1]
            var = None
            for n in ast.walk(fn):
                if (
                    isinstance(n, ast.Assign)
                    and n.value is call
                    and len(n.targets) == 1
                    and isinstance(n.targets[0], ast.Name)
                ):
                    var = n.targets[0].id
            if var is None:
                continue
            for n in ast.walk(fn):
                if not (
                    isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Name)
                    and n.func.id == var
                ):
                    continue
                for pos in donate:
                    if pos >= len(n.args) or not isinstance(n.args[pos], ast.Name):
                        continue
                    donated = n.args[pos].id
                    reads = sorted(
                        m.lineno
                        for m in ast.walk(fn)
                        if isinstance(m, ast.Name)
                        and m.id == donated
                        and isinstance(m.ctx, ast.Load)
                        and m.lineno > n.lineno
                    )
                    stores = {
                        m.lineno
                        for m in ast.walk(fn)
                        if isinstance(m, ast.Name)
                        and m.id == donated
                        and isinstance(m.ctx, ast.Store)
                    }
                    for read_line in reads:
                        if any(n.lineno < s <= read_line for s in stores):
                            break  # rebound before the read: fine
                        yield Finding(
                            rule=self.name,
                            path=sf.rel,
                            line=read_line,
                            message=(
                                f"`{donated}` was donated to `{var}` at line "
                                f"{n.lineno} (donate_argnums={sorted(donate)}) "
                                "and is read here — the buffer no longer "
                                "exists after dispatch"
                            ),
                            context=enclosing_context(sf.tree, n),
                        )
                        break

    def advisories(self, project: Project):
        for sf in project.files:
            if not self.applies(sf.rel):
                continue
            idx = _index(sf)
            for call, _stack in idx.jit_sites:
                if any(kw.arg == "donate_argnums" for kw in call.keywords):
                    continue
                window = range(call.lineno - 3, call.lineno + 2)
                documented = any(
                    "donate" in sf.comments.get(ln, "").lower() for ln in window
                )
                if documented:
                    continue
                yield Finding(
                    rule=self.name,
                    path=sf.rel,
                    line=call.lineno,
                    message=(
                        "call-time jit without donate_argnums — donation "
                        "saves an accumulator copy when the input dies here; "
                        "document the no-donate rationale in a nearby "
                        "comment if it is deliberate"
                    ),
                    context=enclosing_context(sf.tree, call),
                )
