"""The six mdrqlint rules (DESIGN.md §12).

Each rule encodes an invariant this repo's perf/correctness story depends on:

==================  =========================================================
rule id             invariant
==================  =========================================================
host-sync           device->host transfers route through ``ops.device_get``
                    (counted), never raw ``np.asarray``/``float``/``int``/
                    ``bool``/``.item`` coercions of device values or raw
                    ``jax.device_get``/``block_until_ready``
uncounted-launch    ``jax.jit``/``pallas_call`` entry points in ``kernels/``
                    and ``core/`` are registered via ``ops.counted``
raw-shard-map       ``shard_map`` only via ``core.distributed
                    .shard_map_compat`` (ROADMAP standing rule)
sentinel            no hardcoded ``3e38``-family extrema / ``inf``-into-
                    unknown-dtype casts; use ``repro.numerics`` or
                    ``core.types.finite_query_bounds``
lock-discipline     attrs ever written under ``self._lock``/``_ingest_lock``
                    are never written off-lock (outside ``__init__``);
                    ``_state`` swaps are single assignments under the ingest
                    lock; ``_state`` is never mutated in place
registry-hygiene    ``@register_result_spec`` classes are frozen dataclasses
                    (they ride jit static args); registry classes carry no
                    mutable class-level defaults
==================  =========================================================

The host-sync rule is a *taint-lite* dataflow pass: device values enter a
function through counted ``ops.*`` calls, jit-bound callables (including
``self.fn = jax.jit(...)`` attributes), bare ``pallas_call``, or functions
that return tainted values; taint propagates through assignment/unpacking/
subscripts/arithmetic and through calls carrying tainted arguments;
``ops.device_get`` launders taint (it *is* the counted sync).

v2 (whole-program): with a ``ProjectContext`` present (the runner always
builds one), tainted-returning functions are computed as a *project-wide*
fixpoint over the call graph — a device value returned by
``core.scan.ColumnarScan.launch_batch`` stays tainted through a ``serve/``
helper that calls it, aliased imports (``from repro.kernels import ops as
o``) resolve to the counted registry, and ``self.<attr>.method(...)`` calls
resolve through inferred attribute types. Per-file analysis remains the
fallback when no project is attached.

Three kernel-contract rules (``kernel-tile``, ``kernel-dtype``,
``note-trace``) live in ``analysis.contracts`` and are re-exported through
``ALL_RULES`` here.
"""
from __future__ import annotations

import ast
from typing import Optional

from repro.analysis.engine import (FileContext, Finding, ProjectContext,
                                   Rule)

# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------


def _dotted(node: Optional[ast.AST]) -> Optional[str]:
    """'jax.jit' for Attribute chains, 'x' for Name, else None."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return f"{base}.{node.attr}" if base else None
    return None


def _is_jit_expr(node: ast.AST) -> bool:
    """True for ``jax.jit(...)`` and ``functools.partial(jax.jit, ...)``."""
    if not isinstance(node, ast.Call):
        return False
    f = _dotted(node.func)
    if f in ("jax.jit", "jit"):
        return True
    if f in ("functools.partial", "partial") and node.args:
        return _dotted(node.args[0]) in ("jax.jit", "jit")
    return False


def _has_jit_decorator(fn: ast.AST) -> bool:
    for d in getattr(fn, "decorator_list", []):
        if _dotted(d) in ("jax.jit", "jit") or _is_jit_expr(d):
            return True
    return False


_COUNTED_NAMES = {"counted", "_counted", "ops.counted"}


def _counted_wrapped_names(tree: ast.AST) -> set[str]:
    """Names F registered by ``counted(...)(F)`` / ``@counted(...)`` forms."""
    out: set[str] = set()
    for node in ast.walk(tree):
        # X = counted("name", "doc")(F)  /  bare  counted(...)(F)
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Call)
                and _dotted(node.func.func) in _COUNTED_NAMES):
            for a in node.args:
                n = _dotted(a)
                if n:
                    out.add(n)
        # @counted("name", "doc") decorator on a def
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for d in node.decorator_list:
                if (isinstance(d, ast.Call)
                        and _dotted(d.func) in _COUNTED_NAMES):
                    out.add(node.name)
    return out


def _in_repro(posix: str) -> bool:
    return "/repro/" in posix or posix.startswith("repro/")


def _in_sync_scope(posix: str) -> bool:
    """host-sync scope: the package plus the driver trees that consume it —
    an uncounted coercion in ``benchmarks/`` corrupts the very numbers the
    benchmark reports, so the rule covers them too."""
    if _in_repro(posix):
        return True
    return any(f"/{root}/" in posix or posix.startswith(f"{root}/")
               for root in ("benchmarks", "examples"))


# ---------------------------------------------------------------------------
# rule 1: host-sync — taint-lite device->host coercion check
# ---------------------------------------------------------------------------

# ops.* helpers that return HOST data (or are pure bookkeeping): calls to
# these are not device-value sources, and device_get launders taint.
_OPS_HOST_FNS = {"device_get", "counter", "counters", "reset_counters",
                 "use_xla", "set_backend", "default_interpret", "counted",
                 "note_trace", "trace_log", "reset_trace_log", "aot_capture",
                 "aot_cache_size", "aot_cache_keys", "clear_aot_cache",
                 "aot_counters"}
_RAW_SYNC_FNS = {"jax.device_get", "jax.block_until_ready"}
_CAST_SINKS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
               "float", "int", "bool"}


class _FnTaint:
    """One function's taint pass: flags sinks fed by device values."""

    def __init__(self, rule: "HostSyncRule", ctx: FileContext,
                 jit_names: set[str], jit_attrs: set[str],
                 tainted_returning: set[str], collect_only: bool,
                 xmod: "Optional[_CrossModule]" = None):
        self.rule = rule
        self.ctx = ctx
        self.jit_names = jit_names
        self.jit_attrs = jit_attrs
        self.tainted_returning = tainted_returning
        self.collect_only = collect_only
        self.xmod = xmod
        self.tainted: set[str] = set()
        self.returns_tainted = False
        self.findings: list[Finding] = []

    # -- statements ---------------------------------------------------------
    def run(self, fn: ast.AST) -> None:
        body = getattr(fn, "body", [])
        # two passes: monotone taint set converges for use-before-def within
        # loops; findings only recorded on the second pass
        self.collecting = True
        self.block(body)
        self.collecting = False
        if not self.collect_only:
            self.block(body)

    def block(self, stmts: list[ast.stmt]) -> None:
        for s in stmts:
            self.stmt(s)

    def stmt(self, s: ast.stmt) -> None:
        if isinstance(s, ast.Assign):
            t = self.expr(s.value)
            for tgt in s.targets:
                self.bind(tgt, t)
        elif isinstance(s, ast.AugAssign):
            t = self.expr(s.value) or self.expr(s.target)
            self.bind(s.target, t)
        elif isinstance(s, ast.AnnAssign):
            if s.value is not None:
                self.bind(s.target, self.expr(s.value))
        elif isinstance(s, ast.Return):
            if s.value is not None and self.expr(s.value):
                self.returns_tainted = True
        elif isinstance(s, ast.Expr):
            self.expr(s.value)
        elif isinstance(s, (ast.If, ast.While)):
            self.expr(s.test)
            self.block(s.body)
            self.block(s.orelse)
        elif isinstance(s, ast.For):
            if self.expr(s.iter):
                self.bind(s.target, True)
            self.block(s.body)
            self.block(s.orelse)
        elif isinstance(s, (ast.With, ast.AsyncWith)):
            for item in s.items:
                t = self.expr(item.context_expr)
                if item.optional_vars is not None:
                    self.bind(item.optional_vars, t)
            self.block(s.body)
        elif isinstance(s, ast.Try):
            self.block(s.body)
            for h in s.handlers:
                self.block(h.body)
            self.block(s.orelse)
            self.block(s.finalbody)
        elif isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.ClassDef)):
            pass  # nested scopes analyzed separately
        else:
            for child in ast.iter_child_nodes(s):
                if isinstance(child, ast.expr):
                    self.expr(child)

    def bind(self, tgt: ast.AST, tainted: bool) -> None:
        if isinstance(tgt, (ast.Tuple, ast.List)):
            for e in tgt.elts:
                self.bind(e, tainted)
            return
        if isinstance(tgt, ast.Starred):
            self.bind(tgt.value, tainted)
            return
        name = _dotted(tgt)
        if tainted and name:
            self.tainted.add(name)

    # -- expressions --------------------------------------------------------
    def flag(self, node: ast.AST, message: str) -> None:
        if not self.collecting and not self.collect_only:
            self.findings.append(self.rule.finding(self.ctx, node, message))

    def expr(self, e: Optional[ast.AST]) -> bool:
        if e is None or isinstance(e, ast.Constant):
            return False
        if isinstance(e, ast.Name):
            return e.id in self.tainted
        if isinstance(e, ast.Attribute):
            d = _dotted(e)
            return self.expr(e.value) or (d in self.tainted)
        if isinstance(e, ast.Call):
            return self.call(e)
        if isinstance(e, ast.Lambda):
            return False  # opaque; bodies get no device values in this repo
        # generic: any tainted child taints the expression
        t = False
        for child in ast.iter_child_nodes(e):
            if isinstance(child, (ast.expr, ast.comprehension, ast.keyword,
                                  ast.arguments)):
                t = self.expr_child(child) or t
        return t

    def expr_child(self, child: ast.AST) -> bool:
        if isinstance(child, ast.keyword):
            return self.expr(child.value)
        if isinstance(child, ast.comprehension):
            t = self.expr(child.iter)
            if t:
                self.bind(child.target, True)
            for cond in child.ifs:
                self.expr(cond)
            return t
        if isinstance(child, ast.arguments):
            return False
        return self.expr(child)

    def call(self, e: ast.Call) -> bool:
        fname = _dotted(e.func) or ""
        short = fname.rsplit(".", 1)[-1]

        # blessed: the counted sync returns host data and launders taint —
        # under any alias ("device_get" is unambiguous in this codebase)
        if short == "device_get" and not fname.startswith("jax"):
            for a in list(e.args) + [k.value for k in e.keywords]:
                self.expr(a)
            return False

        # raw sync APIs: always a finding in scoped files
        if fname in _RAW_SYNC_FNS:
            self.flag(e, f"raw {fname} — route device->host reads through "
                         "ops.device_get so the sync is counted")
        if isinstance(e.func, ast.Attribute) \
                and e.func.attr == "block_until_ready":
            self.flag(e, "raw .block_until_ready() — use ops.device_get "
                         "(or obs.tracing spans) so the sync is counted")

        args_tainted = any(self.expr(a) for a in e.args) | \
            any(self.expr(k.value) for k in e.keywords)
        base_tainted = (isinstance(e.func, ast.Attribute)
                        and self.expr(e.func.value))

        # sinks: host coercions of device values
        if fname in _CAST_SINKS and args_tainted:
            self.flag(e, f"uncounted host sync: {short}() coerces a device "
                         "value — use ops.device_get")
        if isinstance(e.func, ast.Attribute) and e.func.attr == "item" \
                and base_tainted:
            self.flag(e, "uncounted host sync: .item() on a device value — "
                         "use ops.device_get")

        # sources: counted kernel entry points and jit-bound callables
        source = False
        if fname.startswith("ops.") and short not in _OPS_HOST_FNS:
            source = True
        elif fname in self.jit_names or fname in self.tainted_returning:
            source = True
        elif isinstance(e.func, ast.Attribute) \
                and e.func.attr in (self.jit_attrs | self.tainted_returning):
            source = True
        elif short == "pallas_call" or (isinstance(e.func, ast.Call)
                                        and self.expr(e.func)):
            source = True
        elif self.xmod is not None and self.xmod.is_source(fname):
            source = True
        return source or args_tainted or base_tainted


def _module_jit_sets(tree: ast.AST) -> tuple[set[str], set[str]]:
    """(jit-bound names, jit-bound self attrs) for one module tree."""
    jit_names: set[str] = set()   # module-level jit-bound callables
    jit_attrs: set[str] = set()   # self.<attr> = jax.jit(...) anywhere
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if _has_jit_decorator(node):
                jit_names.add(node.name)
        elif isinstance(node, ast.Assign) and _is_jit_expr(node.value):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    jit_names.add(tgt.id)
                elif isinstance(tgt, ast.Attribute):
                    jit_attrs.add(tgt.attr)
    return jit_names, jit_attrs


def _functions_with_class(tree: ast.AST) -> list[tuple[ast.AST,
                                                       Optional[str]]]:
    """Every function def in the tree, with its immediate owning class."""
    out: list[tuple[ast.AST, Optional[str]]] = []
    method_ids = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef):
            for item in node.body:
                if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    out.append((item, node.name))
                    method_ids.add(id(item))
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and id(node) not in method_ids:
            out.append((node, None))
    return out


class _CrossModule:
    """Cross-module source oracle for ``_FnTaint`` (project runs only).

    Resolves a call's dotted name through the project call graph: counted-op
    registrations under any import alias, project functions in the tainted-
    returning fixpoint set, and ``self.<attr>.method(...)`` receivers via
    inferred attribute types.
    """

    def __init__(self, graph, module: str, cls: Optional[str],
                 tainted_quals: set[str]):
        self.graph = graph
        self.module = module
        self.cls = cls
        self.tainted_quals = tainted_quals

    def is_source(self, fname: str) -> bool:
        if not fname:
            return False
        if fname.startswith("self."):
            return self._self_call(fname[len("self."):])
        q = self.graph.resolve(self.module, fname)
        if q is None:
            return False
        return q in self.graph.counted_ops or q in self.tainted_quals

    def _self_call(self, rest: str) -> bool:
        if self.cls is None:
            return False
        cq = f"{self.module}.{self.cls}"
        head, _, meth = rest.partition(".")
        if not meth:   # self.method()
            hit = self.graph.lookup_method(cq, head)
            return hit is not None and hit.qual in self.tainted_quals
        if "." in meth:
            return False
        ci = self.graph.classes.get(cq)
        if ci is None or head not in ci.attr_types:
            return False
        hit = self.graph.lookup_method(ci.attr_types[head], meth)
        return hit is not None and hit.qual in self.tainted_quals


def project_tainted_quals(project: ProjectContext) -> set[str]:
    """Project-wide fixpoint: quals of functions returning device values.

    Cached on the ProjectContext — computed once per run, shared by every
    file's host-sync pass. Monotone (the set only grows), so the sweep
    converges; 6 rounds bounds the deepest cross-module return chain in
    this tree with slack.
    """
    cached = project.cache.get("host_sync_tainted")
    if cached is not None:
        return cached
    graph = project.graph
    rule = HostSyncRule()
    mods = []
    for fctx in project.files:
        mod = graph.modules.get(fctx.module)
        if mod is None:
            continue
        jn, ja = _module_jit_sets(fctx.tree)
        mods.append((fctx, mod, jn, ja, _functions_with_class(fctx.tree)))
    tainted: set[str] = set()
    for _ in range(6):
        changed = False
        for fctx, mod, jn, ja, fns in mods:
            local = {q.rsplit(".", 1)[-1] for q in tainted
                     if q.startswith(mod.name + ".")}
            for fn, cls in fns:
                prefix = f"{mod.name}.{cls}." if cls else f"{mod.name}."
                qual = prefix + fn.name
                if qual in tainted:
                    continue
                xmod = _CrossModule(graph, mod.name, cls, tainted)
                t = _FnTaint(rule, fctx, jn, ja, local, collect_only=True,
                             xmod=xmod)
                t.run(fn)
                if t.returns_tainted:
                    tainted.add(qual)
                    changed = True
        if not changed:
            break
    project.cache["host_sync_tainted"] = tainted
    return tainted


class HostSyncRule(Rule):
    rule_id = "host-sync"
    doc = ("Device->host transfers must route through ops.device_get so the "
           "launch/host-sync counters (and span attribution) stay exact. "
           "Whole-program: taint follows returns across module boundaries.")

    _ALLOWLIST = ("kernels/ops.py",   # the accounting home itself
                  "obs/tracing.py")   # span exit's sanctioned sync

    def check(self, ctx: FileContext) -> list[Finding]:
        if not _in_sync_scope(ctx.posix) or "/analysis/" in ctx.posix:
            return []
        if any(ctx.posix.endswith(a) for a in self._ALLOWLIST):
            return []

        jit_names, jit_attrs = _module_jit_sets(ctx.tree)
        fns = _functions_with_class(ctx.tree)
        functions = [fn for fn, _ in fns]

        if ctx.project is not None:
            graph = ctx.project.graph
            modname = ctx.module
            quals = project_tainted_quals(ctx.project)
            tainted_returning = {q.rsplit(".", 1)[-1] for q in quals
                                 if q.startswith(modname + ".")}
            findings: list[Finding] = []
            for fn, cls in fns:
                xmod = _CrossModule(graph, modname, cls, quals)
                t = _FnTaint(self, ctx, jit_names, jit_attrs,
                             tainted_returning, collect_only=False,
                             xmod=xmod)
                t.run(fn)
                findings.extend(t.findings)
            return findings

        # fallback: same-module-only analysis (no project attached)
        tainted_returning = set()
        for _ in range(2):  # one refinement round catches chained returns
            for fn in functions:
                t = _FnTaint(self, ctx, jit_names, jit_attrs,
                             tainted_returning, collect_only=True)
                t.run(fn)
                if t.returns_tainted:
                    tainted_returning.add(fn.name)
        findings = []
        for fn in functions:
            t = _FnTaint(self, ctx, jit_names, jit_attrs,
                         tainted_returning, collect_only=False)
            t.run(fn)
            findings.extend(t.findings)
        return findings


# ---------------------------------------------------------------------------
# rule 2: uncounted-launch
# ---------------------------------------------------------------------------

class UncountedLaunchRule(Rule):
    rule_id = "uncounted-launch"
    doc = ("jax.jit / pallas_call entry points in kernels/ and core/ must be "
           "registered via ops.counted so launch budgets stay assertable.")

    def check(self, ctx: FileContext) -> list[Finding]:
        if not ("/repro/kernels/" in ctx.posix or "/repro/core/" in ctx.posix
                or ctx.posix.startswith(("repro/kernels/", "repro/core/"))):
            return []
        registered = _counted_wrapped_names(ctx.tree)
        findings: list[Finding] = []
        for node in ctx.tree.body:  # module-level entry points only
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and _has_jit_decorator(node) \
                    and node.name not in registered:
                findings.append(self.finding(
                    ctx, node, f"jit entry point '{node.name}' is not "
                    "registered via ops.counted — its launches are invisible "
                    "to the counter budget"))
            elif isinstance(node, ast.Assign) and _is_jit_expr(node.value):
                for tgt in node.targets:
                    name = _dotted(tgt)
                    if name and name not in registered:
                        findings.append(self.finding(
                            ctx, node, f"jit binding '{name}' is not "
                            "registered via ops.counted — its launches are "
                            "invisible to the counter budget"))
        # bare pallas_call in core/ (kernel *impl* modules in kernels/ are
        # the sanctioned place to build pallas_call wrappers for ops.py)
        if "/core/" in ctx.posix or ctx.posix.startswith("repro/core/"):
            for node in ast.walk(ctx.tree):
                if isinstance(node, ast.Call) \
                        and (_dotted(node.func) or "").endswith("pallas_call"):
                    findings.append(self.finding(
                        ctx, node, "bare pallas_call in core/ — wrap it in a "
                        "kernels/ module and register via ops.counted"))
        return findings


# ---------------------------------------------------------------------------
# rule 3: raw-shard-map
# ---------------------------------------------------------------------------

class RawShardMapRule(Rule):
    rule_id = "raw-shard-map"
    doc = ("shard_map only via core.distributed.shard_map_compat (ROADMAP "
           "standing rule: one place sets the flags every pallas_call "
           "inside a shard_map needs).")

    _MSG = ("raw shard_map — use core.distributed.shard_map_compat "
            "(it turns off the vma check pallas_call outputs cannot pass)")

    def check(self, ctx: FileContext) -> list[Finding]:
        if not _in_repro(ctx.posix) \
                or ctx.posix.endswith("core/distributed.py"):
            return []
        findings = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if "shard_map" in mod or any("shard_map" == a.name
                                             for a in node.names):
                    findings.append(self.finding(ctx, node, self._MSG))
            elif isinstance(node, ast.Import):
                if any("shard_map" in a.name for a in node.names):
                    findings.append(self.finding(ctx, node, self._MSG))
            elif isinstance(node, ast.Attribute) and node.attr == "shard_map":
                base = _dotted(node.value) or ""
                if base.startswith("jax"):
                    findings.append(self.finding(ctx, node, self._MSG))
        return findings


# ---------------------------------------------------------------------------
# rule 4: sentinel
# ---------------------------------------------------------------------------

_CAST_FNS = {"jnp.asarray", "jnp.array", "jnp.full", "jnp.full_like",
             "np.full", "np.full_like"}
_WIDE_DTYPES = {"np.float32", "jnp.float32", "np.float64", "jnp.float64",
                "float", "F32", "F64", "FLOAT32", "FLOAT64"}
_INF_NAMES = {"np.inf", "jnp.inf", "math.inf", "inf"}


def _is_inf_expr(e: ast.AST) -> bool:
    if isinstance(e, ast.UnaryOp) and isinstance(e.op, (ast.USub, ast.UAdd)):
        return _is_inf_expr(e.operand)
    if _dotted(e) in _INF_NAMES:
        return True
    if isinstance(e, ast.Call) and _dotted(e.func) == "float" and e.args:
        a = e.args[0]
        return isinstance(a, ast.Constant) and isinstance(a.value, str) \
            and "inf" in a.value
    return False


class SentinelRule(Rule):
    rule_id = "sentinel"
    doc = ("No hardcoded 3e38-family extrema and no inf into unknown-dtype "
           "casts: f32 extrema round to +-inf under bf16 casts (PR 3 bug). "
           "Use repro.numerics / core.types.finite_query_bounds.")

    _LIMIT = 1e30

    def check(self, ctx: FileContext) -> list[Finding]:
        if not _in_repro(ctx.posix) or "/analysis/" in ctx.posix \
                or ctx.posix.endswith("repro/numerics.py"):
            return []
        findings = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Constant) \
                    and isinstance(node.value, float) \
                    and abs(node.value) >= self._LIMIT \
                    and node.value == node.value:  # not NaN
                findings.append(self.finding(
                    ctx, node, f"hardcoded extreme literal {node.value!r} — "
                    "derive it from the target dtype via repro.numerics "
                    "(finite_min/finite_max/mask_fill); f32-scale extrema "
                    "round to inf under bf16 casts"))
            elif isinstance(node, ast.Call) \
                    and _dotted(node.func) in _CAST_FNS:
                vals = list(node.args) + [k.value for k in node.keywords]
                if not any(_is_inf_expr(v) for v in vals):
                    continue
                dtypes = [_dotted(v) for v in vals]
                if not any(d in _WIDE_DTYPES for d in dtypes if d):
                    findings.append(self.finding(
                        ctx, node, "inf cast into a non-explicit dtype — "
                        "under bf16 this may stay inf where a finite "
                        "sentinel was intended; use repro.numerics or "
                        "core.types.finite_query_bounds"))
        return findings


# ---------------------------------------------------------------------------
# rule 5: lock-discipline
# ---------------------------------------------------------------------------

def _lockish(ctx: FileContext, w: ast.With, needle: str = "_lock") -> bool:
    return any(needle in (ctx.segment(item.context_expr) or "")
               for item in w.items)


class LockDisciplineRule(Rule):
    rule_id = "lock-discipline"
    doc = ("Attributes ever written under self._lock/_ingest_lock are "
           "lock-guarded: off-lock writes (outside __init__) race the "
           "mutable plane. _state swaps must be one assignment under the "
           "ingest lock; _state is never mutated in place.")

    def check(self, ctx: FileContext) -> list[Finding]:
        if not _in_repro(ctx.posix) or "/analysis/" in ctx.posix:
            return []
        findings: list[Finding] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ClassDef):
                findings.extend(self._check_class(ctx, node))
        findings.extend(self._check_state_swaps(ctx))
        return findings

    # -- guarded attribute writes ------------------------------------------
    def _attr_writes(self, fn: ast.AST, ctx: FileContext
                     ) -> list[tuple[str, ast.AST, bool]]:
        """(attr, node, under_lock) for every ``self.X = ...`` write."""
        out: list[tuple[str, ast.AST, bool]] = []

        def walk(stmts, under):
            for s in stmts:
                if isinstance(s, ast.With):
                    walk(s.body, under or _lockish(ctx, s))
                    continue
                if isinstance(s, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                    targets = (s.targets if isinstance(s, ast.Assign)
                               else [s.target])
                    for tgt in targets:
                        parts = (tgt.elts
                                 if isinstance(tgt, (ast.Tuple, ast.List))
                                 else [tgt])
                        for t in parts:
                            base = t
                            if isinstance(base, ast.Subscript):
                                base = base.value
                            if isinstance(base, ast.Attribute) \
                                    and isinstance(base.value, ast.Name) \
                                    and base.value.id == "self":
                                out.append((base.attr, s, under))
                for name in ("body", "orelse", "finalbody"):
                    walk(getattr(s, name, []) or [], under)
                for h in getattr(s, "handlers", []) or []:
                    walk(h.body, under)
        walk(getattr(fn, "body", []), False)
        return out

    def _check_class(self, ctx: FileContext, cls: ast.ClassDef
                     ) -> list[Finding]:
        methods = [n for n in cls.body
                   if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        writes = {m.name: self._attr_writes(m, ctx) for m in methods}
        guarded = {attr for ws in writes.values()
                   for attr, _, under in ws if under}
        findings = []
        for name, ws in writes.items():
            if name == "__init__":
                continue
            for attr, node, under in ws:
                if attr in guarded and not under:
                    findings.append(self.finding(
                        ctx, node, f"'{cls.name}.{attr}' is written under a "
                        "lock elsewhere but mutated here off-lock — this "
                        "races the guarded mutable plane"))
        return findings

    # -- _state swap discipline --------------------------------------------
    def _check_state_swaps(self, ctx: FileContext) -> list[Finding]:
        findings = []

        def walk(stmts, under_ingest, in_init):
            for s in stmts:
                if isinstance(s, ast.With):
                    walk(s.body,
                         under_ingest or _lockish(ctx, s, "_ingest_lock"),
                         in_init)
                    continue
                if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    walk(s.body, False, s.name == "__init__")
                    continue
                if isinstance(s, ast.ClassDef):
                    walk(s.body, False, False)
                    continue
                if isinstance(s, ast.Assign):
                    for tgt in s.targets:
                        # X._state.attr = v  /  X._state.d[k] = v: in-place
                        base = tgt
                        if isinstance(base, ast.Subscript):
                            base = base.value
                        inner = base.value if isinstance(base, ast.Attribute) \
                            else None
                        if isinstance(inner, ast.Attribute) \
                                and inner.attr == "_state":
                            findings.append(self.finding(
                                ctx, s, "in-place mutation of _state — "
                                "engine state is immutable; build a new "
                                "state and swap it in one assignment"))
                        # X._state = v: must be a lone swap under the lock
                        elif isinstance(base, ast.Attribute) \
                                and base.attr == "_state":
                            if len(s.targets) != 1 \
                                    or isinstance(tgt, (ast.Tuple, ast.List)):
                                findings.append(self.finding(
                                    ctx, s, "_state swap must be a single "
                                    "plain assignment (readers snapshot it "
                                    "lock-free)"))
                            elif not (under_ingest or in_init):
                                findings.append(self.finding(
                                    ctx, s, "_state swap outside the ingest "
                                    "lock — concurrent writers can "
                                    "interleave stale states"))
                for name in ("body", "orelse", "finalbody"):
                    walk(getattr(s, name, []) or [], under_ingest, in_init)
                for h in getattr(s, "handlers", []) or []:
                    walk(h.body, under_ingest, in_init)
        walk(ctx.tree.body, False, False)
        return findings


# ---------------------------------------------------------------------------
# rule 6: registry-hygiene
# ---------------------------------------------------------------------------

_REGISTER_DECOS = {"register_result_spec", "register_path"}


class RegistryHygieneRule(Rule):
    rule_id = "registry-hygiene"
    doc = ("Registered ResultSpec classes must be frozen dataclasses (they "
           "ride jit static args: hashability + immutability) and registry "
           "classes must not carry mutable class-level defaults.")

    _REGISTRY_MODULES = ("core/types.py", "core/paths.py")

    def _register_deco(self, cls: ast.ClassDef) -> Optional[str]:
        for d in cls.decorator_list:
            name = _dotted(d.func if isinstance(d, ast.Call) else d) or ""
            short = name.rsplit(".", 1)[-1]
            if short in _REGISTER_DECOS:
                return short
        return None

    def _frozen_dataclass(self, cls: ast.ClassDef) -> bool:
        for d in cls.decorator_list:
            if isinstance(d, ast.Call):
                name = _dotted(d.func) or ""
                if name.rsplit(".", 1)[-1] == "dataclass":
                    for k in d.keywords:
                        if k.arg == "frozen" \
                                and isinstance(k.value, ast.Constant) \
                                and k.value.value is True:
                            return True
        return False

    def check(self, ctx: FileContext) -> list[Finding]:
        findings = []
        in_registry_module = any(ctx.posix.endswith(m)
                                 for m in self._REGISTRY_MODULES)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            deco = self._register_deco(node)
            if deco == "register_result_spec" \
                    and not self._frozen_dataclass(node):
                findings.append(self.finding(
                    ctx, node, f"'{node.name}' is registered via "
                    "register_result_spec but is not a frozen dataclass — "
                    "specs ride jit static args and must be hashable and "
                    "immutable"))
            if deco or in_registry_module:
                for stmt in node.body:
                    if isinstance(stmt, (ast.Assign, ast.AnnAssign)):
                        val = stmt.value
                        if isinstance(val, (ast.List, ast.Dict, ast.Set)):
                            findings.append(self.finding(
                                ctx, stmt, f"mutable class-level default on "
                                f"'{node.name}' — shared across every "
                                "instance (and unhashable under jit static "
                                "args); use dataclasses.field or a tuple"))
        return findings


# ---------------------------------------------------------------------------
# rule 7: thread-boundary
# ---------------------------------------------------------------------------

class ThreadBoundaryRule(Rule):
    rule_id = "thread-boundary"
    doc = ("Pipelined-serving stage discipline (DESIGN.md §13): a "
           "@device_stage function never calls ops.device_get (the counted "
           "sync belongs to the finalizer thread) and never parks a device "
           "value on self — in-flight payloads cross threads only inside a "
           "PendingBatch riding the bounded backlog queue.")

    # calls whose results carry device values in a device-stage function:
    # counted kernel entry points and the split-protocol launch
    _DEVICEY_METHODS = {"launch_batch"}

    @staticmethod
    def _stage(fn: ast.AST) -> Optional[str]:
        for d in getattr(fn, "decorator_list", []):
            name = _dotted(d) or ""
            short = name.rsplit(".", 1)[-1]
            if short == "device_stage":
                return "device"
            if short == "finalizer_stage":
                return "finalize"
        return None

    def check(self, ctx: FileContext) -> list[Finding]:
        if not _in_repro(ctx.posix) or "/analysis/" in ctx.posix:
            return []
        findings: list[Finding] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and self._stage(node) == "device":
                findings.extend(self._check_device(ctx, node))
        return findings

    def _check_device(self, ctx: FileContext, fn: ast.AST) -> list[Finding]:
        findings: list[Finding] = []
        tainted: set[str] = set()

        def is_tainted(e: ast.AST) -> bool:
            if isinstance(e, ast.Name):
                return e.id in tainted
            if isinstance(e, ast.Call):
                fname = _dotted(e.func) or ""
                short = fname.rsplit(".", 1)[-1]
                if fname.startswith("ops.") and short not in _OPS_HOST_FNS:
                    return True
                if short in self._DEVICEY_METHODS:
                    return True
                return (any(is_tainted(a) for a in e.args)
                        or any(is_tainted(k.value) for k in e.keywords))
            if isinstance(e, (ast.Tuple, ast.List)):
                return any(is_tainted(x) for x in e.elts)
            if isinstance(e, (ast.Attribute, ast.Subscript, ast.Starred)):
                return is_tainted(e.value)
            return False

        # two monotone passes converge name taint (use-before-def in loops)
        for _ in range(2):
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) and is_tainted(node.value):
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            tainted.add(tgt.id)
                        elif isinstance(tgt, (ast.Tuple, ast.List)):
                            for e in tgt.elts:
                                if isinstance(e, ast.Name):
                                    tainted.add(e.id)

        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                fname = _dotted(node.func) or ""
                if fname.rsplit(".", 1)[-1] == "device_get":
                    findings.append(self.finding(
                        ctx, node, "ops.device_get in a @device_stage "
                        "function — the counted host sync belongs to the "
                        "finalizer thread; hand the in-flight payload across "
                        "the backlog queue instead"))
            elif isinstance(node, ast.Assign):
                if not is_tainted(node.value):
                    continue
                for tgt in node.targets:
                    base = tgt.value if isinstance(tgt, ast.Subscript) else tgt
                    if isinstance(base, ast.Attribute) \
                            and isinstance(base.value, ast.Name) \
                            and base.value.id == "self":
                        findings.append(self.finding(
                            ctx, node, f"device value parked on "
                            f"'self.{base.attr}' in a @device_stage function "
                            "— device values cross threads only through the "
                            "bounded backlog queue (put a PendingBatch, not "
                            "an attribute)"))
        return findings


# imported at the bottom: contracts.py needs the helpers defined above
from repro.analysis.contracts import CONTRACT_RULES  # noqa: E402

ALL_RULES: tuple[Rule, ...] = (
    HostSyncRule(), UncountedLaunchRule(), RawShardMapRule(), SentinelRule(),
    LockDisciplineRule(), RegistryHygieneRule(), ThreadBoundaryRule(),
) + CONTRACT_RULES
