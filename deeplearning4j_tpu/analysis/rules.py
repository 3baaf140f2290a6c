"""graftlint rule set R1-R6: the hazards of Python-over-XLA step paths.

Shared machinery first: ``ModuleFacts`` classifies every function in a
module as *traced* (reachable from a jit/shard_map/grad wrapper — its body
runs under a tracer), *step-loop* (host code that drives a train-step
callable per iteration), or plain host code, and runs a light lexical
taint pass marking names bound from step-fn results. The rules then only
fire where the hazard is real:

* a ``float()`` in a traced body is a tracer leak (R1, always wrong);
* a ``float()`` on a step result inside a fit/round loop is a
  per-iteration sync (R1, fix = accumulate on device or fetch one step
  late — ``nn/multilayer.py`` TBPTT and ``telemetry/scorepipe.py`` are
  the sanctioned patterns);
* the same ``float()`` in a one-shot ``score()`` API is fine and is not
  flagged.

Static analysis over a dynamic language is heuristic by design: the
classifier keys on how this repo actually builds step functions
(``make_train_step``/``make_tbptt_step`` makers, ``*_step_fn`` caches,
jit/shard_map wrapping) rather than attempting whole-program inference.
New findings that are deliberate carry a line suppression with a
justification; pre-existing debt lives in the committed baseline.
"""

from __future__ import annotations

import ast
import re

from deeplearning4j_tpu.analysis.core import LintModule, Rule, register

# ----------------------------------------------------------------------
# classification tables
# ----------------------------------------------------------------------

#: canonical dotted names whose call-argument functions become traced
_TRACING_WRAPPERS = (
    "jax.jit", "jax.pmap", "jax.vmap", "jax.grad", "jax.value_and_grad",
    "jax.checkpoint", "jax.remat", "jax.lax.scan", "jax.lax.while_loop",
    "jax.lax.fori_loop", "jax.lax.cond", "jax.lax.map", "pjit",
)
#: suffix-matched wrappers (compat shims re-export under many roots)
_TRACING_SUFFIXES = (".shard_map", ".pallas_call", ".jit", ".pmap",
                     ".value_and_grad", ".grad", ".checkpoint")

#: callee names that mark the calling (host) function as a step loop
_STEP_EXACT = {
    "step", "step_fn", "train_step", "tbptt_step", "split_step",
    "make_train_step", "make_tbptt_step",
}
_STEP_SUFFIXES = ("_step", "step_fn", "_split_fn")
#: ...except streaming-inference timesteps, whose callers legitimately
#: sync per call (results must reach the host)
_STEP_EXCLUDE_SUFFIX = ("time_step",)

#: single-argument builtins that force a device->host transfer on a tracer
#: or concrete device array
_SYNC_BUILTINS = {"float", "int", "bool", "complex"}
#: dotted calls that are explicit syncs
_SYNC_DOTTED = {"numpy.asarray", "numpy.array", "jax.device_get",
                "jax.block_until_ready"}
#: method names that sync their receiver
_SYNC_METHODS = {"item", "tolist", "block_until_ready", "__array__"}

#: attribute accesses that are static metadata, never traced values
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "axis_names"}

#: telemetry entry points that ARE safe inside traced code (pure jnp math
#: designed to fuse into the step; see telemetry/health.py)
_PURE_TELEMETRY = {"health_stats", "tree_sq_sum", "any_nonfinite"}

_IMPURE_DOTTED_PREFIXES = ("time.", "numpy.random.", "random.",
                           "datetime.")
_IMPURE_NAME_CALLS = {"print", "open", "input"}
_IMPURE_LOG_ROOTS = {"logger", "logging", "log"}
_IMPURE_METRIC_METHODS = {"inc", "dec", "observe", "set", "note",
                          "annotate", "dump", "record"}

_BACKEND_CALLS = {"memory_stats", "live_arrays", "memory_info",
                  "defragment"}

_MUTATING_METHODS = {"append", "extend", "insert", "remove", "pop",
                     "clear", "update", "add", "discard", "appendleft",
                     "popleft", "popitem", "setdefault"}


def _is_step_callee(name):
    if name is None:
        return False
    short = name.rsplit(".", 1)[-1]
    if short.endswith(_STEP_EXCLUDE_SUFFIX):
        return False
    return (short in _STEP_EXACT
            or short.endswith(_STEP_SUFFIXES))


def _callee_name(call, mod):
    """Short name of a Call's target: bare name, attr name, or None."""
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def _is_tracing_wrapper(dotted):
    if dotted is None:
        return False
    return (dotted in _TRACING_WRAPPERS
            or dotted.endswith(_TRACING_SUFFIXES))


# ----------------------------------------------------------------------
# per-module facts
# ----------------------------------------------------------------------

class ModuleFacts:
    """Traced / step-loop classification + step-result taint, computed
    once per module and shared by every rule (attached to the LintModule
    so N rules don't re-derive it N times)."""

    def __init__(self, mod: LintModule):
        self.mod = mod
        self.functions = [n for n in ast.walk(mod.tree)
                          if isinstance(n, (ast.FunctionDef,
                                            ast.AsyncFunctionDef))]
        self._by_name = {}
        self._encl_fn = {}
        self._encl_cls = {}
        for fn in self.functions:
            self._by_name.setdefault(fn.name, []).append(fn)
            self._encl_fn[fn] = mod.enclosing_function(fn)
            self._encl_cls[fn] = self._class_of(fn)
        self.traced = self._find_traced()
        self.steploop = self._find_steploops()
        self.taint = {fn: self._taint_pass(fn) for fn in self.steploop}

    # -- traced set -----------------------------------------------------

    def _find_traced(self):
        mod = self.mod
        roots = set()
        for fn in self.functions:
            for dec in fn.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                if _is_tracing_wrapper(mod.dotted(target)):
                    roots.add(fn)
        # functions handed to jit/shard_map/grad/scan calls by name
        for call in (n for n in ast.walk(mod.tree)
                     if isinstance(n, ast.Call)):
            if not _is_tracing_wrapper(mod.dotted(call.func)):
                continue
            for arg in list(call.args) + [k.value for k in call.keywords]:
                for fn in self._resolve_callable(arg, site=call):
                    roots.add(fn)
        # transitive closure over same-module call edges + nested defs
        traced = set(roots)
        changed = True
        while changed:
            changed = False
            for fn in self.functions:
                if fn in traced:
                    continue
                encl = self.mod.enclosing_function(fn)
                if encl is not None and encl in traced:
                    traced.add(fn)
                    changed = True
            for fn in list(traced):
                for call in (n for n in ast.walk(fn)
                             if isinstance(n, ast.Call)):
                    for callee in self._resolve_callable(call.func,
                                                         site=call):
                        if callee not in traced:
                            traced.add(callee)
                            changed = True
        return traced

    def _class_of(self, node):
        for a in self.mod.ancestors(node):
            if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return None  # a def nested in a method is not a method
            if isinstance(a, ast.ClassDef):
                return a
        return None

    def _resolve_callable(self, node, site):
        """Same-module functions a Name / ``self.x`` / ``cls.x`` node may
        refer to, resolved LEXICALLY from ``site``: a bare name only
        reaches defs visible by scoping (nested in an enclosing function,
        or module level), and ``self.x`` only reaches methods of the
        class the site sits in — so a jitted nested ``step`` never taints
        a same-named public method."""
        if isinstance(node, ast.Name):
            chain = []
            f = self.mod.enclosing_function(site)
            while f is not None:
                chain.append(f)
                f = self._encl_fn.get(f)
            chain.append(None)  # module scope
            for scope in chain:
                hits = [fn for fn in self._by_name.get(node.id, [])
                        if self._encl_fn.get(fn) is scope
                        and (scope is not None
                             or self._encl_cls.get(fn) is None)]
                if hits:
                    return hits
            return []
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id in ("self", "cls")):
            site_cls = self._class_of_site(site)
            hits = [fn for fn in self._by_name.get(node.attr, [])
                    if self._encl_cls.get(fn) is not None
                    and (site_cls is None
                         or self._encl_cls.get(fn) is site_cls)]
            return hits
        return []

    def _class_of_site(self, node):
        for a in self.mod.ancestors(node):
            if isinstance(a, ast.ClassDef):
                return a
        return None

    # -- step loops -----------------------------------------------------

    def _find_steploops(self):
        out = set()
        for fn in self.functions:
            if fn in self.traced:
                continue
            for call in (n for n in ast.walk(fn)
                         if isinstance(n, ast.Call)):
                if self.mod.enclosing_function(call) is not fn:
                    continue  # nested defs classified on their own
                if _is_step_callee(_callee_name(call, self.mod)):
                    out.add(fn)
                    break
        return out

    # -- step-result taint ---------------------------------------------

    def _taint_pass(self, fn):
        """Names (and ``self.x`` attrs) bound from step-fn call results,
        by one lexical pass over the function's assignments. A sync
        construct's own result is host data and clears the taint."""
        tainted = set()

        def expr_tainted(node):
            for n in ast.walk(node):
                if isinstance(n, ast.Call):
                    if _is_step_callee(_callee_name(n, self.mod)):
                        return True
                if isinstance(n, ast.Name) and n.id in tainted:
                    return True
                if (isinstance(n, ast.Attribute)
                        and isinstance(n.value, ast.Name)
                        and n.value.id == "self"
                        and f"self.{n.attr}" in tainted):
                    return True
            return False

        def target_keys(t):
            if isinstance(t, ast.Name):
                return [t.id]
            if (isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"):
                return [f"self.{t.attr}"]
            if isinstance(t, (ast.Tuple, ast.List)):
                keys = []
                for e in t.elts:
                    keys.extend(target_keys(e))
                return keys
            if isinstance(t, ast.Starred):
                return target_keys(t.value)
            return []

        for node in ast.walk(fn):
            if self.mod.enclosing_function(node) is not fn:
                continue
            if isinstance(node, ast.Assign):
                if _sync_call_kind(node.value, self.mod):
                    continue  # float(loss) etc: the result is host data
                if expr_tainted(node.value):
                    for t in node.targets:
                        tainted.update(target_keys(t))
            elif isinstance(node, ast.AugAssign):
                if expr_tainted(node.value):
                    tainted.update(target_keys(node.target))
        return tainted

    def expr_tainted(self, fn, node):
        tainted = self.taint.get(fn, set())
        for n in ast.walk(node):
            if isinstance(n, ast.Call):
                if _is_step_callee(_callee_name(n, self.mod)):
                    return True
            if isinstance(n, ast.Name) and n.id in tainted:
                return True
            if (isinstance(n, ast.Attribute)
                    and isinstance(n.value, ast.Name)
                    and n.value.id == "self"
                    and f"self.{n.attr}" in tainted):
                return True
        return False


def _facts(mod: LintModule) -> ModuleFacts:
    f = getattr(mod, "_gl_facts", None)
    if f is None:
        f = mod._gl_facts = ModuleFacts(mod)
    return f


def _sync_call_kind(node, mod):
    """If ``node`` is a sync construct call, return ("name", arg_node);
    else None. arg_node is the synced expression (or None)."""
    if not isinstance(node, ast.Call):
        return None
    f = node.func
    if isinstance(f, ast.Name) and f.id in _SYNC_BUILTINS:
        if len(node.args) == 1:
            return (f.id, node.args[0])
        return None
    dotted = mod.dotted(f)
    if dotted in _SYNC_DOTTED:
        arg = node.args[0] if node.args else None
        return (dotted, arg)
    if isinstance(f, ast.Attribute) and f.attr in _SYNC_METHODS:
        return (f".{f.attr}()", f.value)
    return None


def _is_static_expr(node, mod=None):
    """Expressions whose value is static under a tracer: literals,
    shape/dtype metadata, and shape arithmetic. ``int(x.shape[0])`` or
    ``int(np.prod(shape[1:]))`` in a jitted body is fine."""
    if node is None:
        return True
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr in _STATIC_ATTRS:
            return True
        if isinstance(n, ast.Call):
            if isinstance(n.func, ast.Name) and n.func.id == "len":
                return True
            if mod is not None and mod.dotted(n.func) in (
                    "numpy.prod", "math.prod", "numpy.ndim"):
                return True
    return isinstance(node, ast.Constant)


# ----------------------------------------------------------------------
# R1: hidden host syncs
# ----------------------------------------------------------------------

@register
class HostSyncRule(Rule):
    name = "R1"
    slug = "host-sync"
    description = (
        "implicit device->host sync in the step path: float()/int()/"
        "np.asarray/.item()/.tolist() on traced values inside jitted "
        "functions, or per-iteration on step results inside fit/round "
        "loops (fix: accumulate on device, or fetch one step late via "
        "telemetry.scorepipe / telemetry.health)")

    def check(self, mod: LintModule):
        facts = _facts(mod)
        for fn in facts.traced:
            for node in ast.walk(fn):
                if mod.enclosing_function(node) is not fn:
                    continue
                kind = _sync_call_kind(node, mod)
                if kind is None:
                    continue
                if _is_static_expr(kind[1], mod):
                    continue
                yield mod.finding(
                    self.name, self.slug, node,
                    f"{kind[0]} inside traced code forces a device->host "
                    "sync at trace/run time; keep the value on device")
        for fn in facts.steploop:
            for node in ast.walk(fn):
                if mod.enclosing_function(node) is not fn:
                    continue
                kind = _sync_call_kind(node, mod)
                if kind is None or kind[1] is None:
                    continue
                if not mod.in_loop_within(node, fn):
                    continue
                if not facts.expr_tainted(fn, kind[1]):
                    continue
                yield mod.finding(
                    self.name, self.slug, node,
                    f"per-iteration {kind[0]} on a train-step result "
                    "forces one device->host sync per step; accumulate "
                    "on device or fetch one step late "
                    "(telemetry.scorepipe.ScorePipeline)")


# ----------------------------------------------------------------------
# R2: Python control flow on traced values
# ----------------------------------------------------------------------

@register
class TracedBranchRule(Rule):
    name = "R2"
    slug = "traced-branch"
    description = (
        "Python if/while on a traced value inside a jitted body — a "
        "TracerBoolConversionError at runtime (or a silent trace-time "
        "constant); use jax.lax.cond/select or hoist the decision")

    def check(self, mod: LintModule):
        facts = _facts(mod)
        for fn in facts.traced:
            params = {a.arg for a in (fn.args.posonlyargs + fn.args.args
                                      + fn.args.kwonlyargs)} - {"self", "cls"}
            derived = set(params)
            for node in ast.walk(fn):
                if mod.enclosing_function(node) is not fn:
                    continue
                if isinstance(node, ast.Assign) and not _sync_call_kind(
                        node.value, mod):
                    if any(isinstance(n, ast.Name) and n.id in derived
                           for n in ast.walk(node.value)):
                        for t in node.targets:
                            if isinstance(t, ast.Name):
                                derived.add(t.id)
            for node in ast.walk(fn):
                if mod.enclosing_function(node) is not fn:
                    continue
                if not isinstance(node, (ast.If, ast.While)):
                    continue
                hit = self._traced_test(node.test, derived, mod)
                if hit is not None:
                    yield mod.finding(
                        self.name, self.slug, node,
                        f"branch on {hit} inside traced code; use "
                        "jax.lax.cond/jnp.where or move the decision "
                        "outside the jitted function")

    @staticmethod
    def _traced_test(test, derived, mod):
        """What makes this test traced-value-dependent, or None.

        Deliberately narrow: bare-name truthiness (pytree structure
        checks like ``if p:``), ``is None`` sentinels, and shape/ndim
        metadata comparisons are all legitimate static control flow."""
        for n in ast.walk(test):
            if isinstance(n, ast.Call):
                dotted = mod.dotted(n.func) or ""
                if dotted.startswith(("jax.numpy.", "jax.lax.")) \
                        or dotted in ("jax.numpy", "jax.lax"):
                    return f"a {dotted}(...) result"
            if isinstance(n, ast.Compare):
                if all(isinstance(op, (ast.Is, ast.IsNot))
                       for op in n.ops):
                    continue
                operands = [n.left] + list(n.comparators)
                if any(_is_static_expr(o) and not isinstance(o, ast.Constant)
                       for o in operands):
                    continue  # shape/metadata comparison
                for o in operands:
                    for m in ast.walk(o):
                        if isinstance(m, ast.Name) and m.id in derived:
                            return f"traced value {m.id!r}"
        return None


# ----------------------------------------------------------------------
# R3: recompile hazards
# ----------------------------------------------------------------------

@register
class RecompileRule(Rule):
    name = "R3"
    slug = "recompile"
    description = (
        "recompile hazard: jax.jit/shard_map built inside a loop (one "
        "fresh XLA compile per iteration), jit of an inline lambda "
        "rebuilt per call, or a raw .lower().compile() chain outside "
        "utils/compile_cache — AOT compiles that bypass aot_compile() "
        "can never be served from a warm manifest, so every restart "
        "pays them again")

    _WRAP_ONLY = ("jax.jit", "jax.pmap")

    #: the one blessed .lower().compile() site — everything else routes
    #: through aot_compile (deliberate one-shots use the split
    #: lowered/compile idiom, which this matcher leaves alone)
    _CACHE_TIER = "utils/compile_cache.py"

    def check(self, mod: LintModule):
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            chained = self._lower_compile_chain(node, mod)
            if chained:
                yield chained
            dotted = mod.dotted(node.func)
            if not _is_tracing_wrapper(dotted):
                continue
            if dotted is not None and dotted.startswith("jax.lax."):
                continue  # scan/cond INSIDE traced code are fine in loops
            fn = mod.enclosing_function(node)
            if fn is not None and fn in _facts(mod).traced:
                # inside traced code the loop unrolls ONCE at trace time;
                # per-layer jax.checkpoint wrapping is the remat idiom
                continue
            if fn is not None and mod.in_loop_within(node, fn) \
                    and not self._feeds_aot_compile(node, mod):
                # a jit whose result flows into aot_compile() in the same
                # loop body is the AUTOTUNE idiom:
                # one deliberate, manifest-aware compile per candidate is
                # the search working, not a recompile hazard — the
                # blessed site counts and caches it
                yield mod.finding(
                    self.name, self.slug, node,
                    f"{dotted or 'jit'} built inside a loop: every "
                    "iteration pays a fresh trace+compile; hoist and "
                    "cache the jitted callable (or route deliberate "
                    "per-candidate compiles through "
                    "utils/compile_cache.aot_compile)")
            if (dotted in self._WRAP_ONLY and node.args
                    and isinstance(node.args[0], ast.Lambda)
                    and fn is not None):
                yield mod.finding(
                    self.name, self.slug, node,
                    f"{dotted}(lambda ...) inside a function body builds "
                    "a fresh callable (and compile-cache entry) per call; "
                    "define the function once at module/class scope")

    @staticmethod
    def _is_aot_compile(call, mod):
        dotted = mod.dotted(call.func) or ""
        return dotted == "aot_compile" or dotted.endswith(".aot_compile")

    def _feeds_aot_compile(self, node, mod):
        """True when the jit built at ``node`` is handed to the blessed
        ``utils/compile_cache.aot_compile`` site within the same loop —
        directly (``aot_compile(jax.jit(f), ...)``) or through a local
        binding (``jitted = jax.jit(f); ex, _ = aot_compile(jitted,
        ...)``). That is the tuner's measurement harness compiling one
        candidate per iteration through the manifest-aware site — a
        deliberate compile, not a hazard."""
        parent = mod.parent(node)
        if (isinstance(parent, ast.Call)
                and self._is_aot_compile(parent, mod)
                and any(a is node for a in parent.args)):
            return True
        names = set()
        for a in mod.ancestors(node):
            if isinstance(a, ast.Assign):
                names.update(t.id for t in a.targets
                             if isinstance(t, ast.Name))
                break
            if isinstance(a, (ast.For, ast.While, ast.AsyncFor,
                              ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                break
        if not names:
            return False
        loop = next((a for a in mod.ancestors(node)
                     if isinstance(a, (ast.For, ast.While, ast.AsyncFor))),
                    None)
        if loop is None:
            return False
        return any(
            isinstance(n, ast.Call) and self._is_aot_compile(n, mod)
            and any(isinstance(a, ast.Name) and a.id in names
                    for a in n.args)
            for n in ast.walk(loop))

    def _lower_compile_chain(self, node, mod):
        """A chained ``<jit>.lower(...).compile(...)`` call: outside the
        cache tier it produces an executable the warm manifest can never
        serve (utils/compile_cache.aot_compile is the one blessed site —
        it checks the manifest first and serializes live compiles back)."""
        f = node.func
        if not (isinstance(f, ast.Attribute) and f.attr == "compile"
                and isinstance(f.value, ast.Call)
                and isinstance(f.value.func, ast.Attribute)
                and f.value.func.attr == "lower"):
            return None
        path = str(mod.path).replace("\\", "/")
        if path == self._CACHE_TIER or path.endswith("/" + self._CACHE_TIER):
            return None  # the blessed site itself (anchored on a path
            #              separator so myutils/compile_cache.py is NOT
            #              silently exempt)
        return mod.finding(
            self.name, self.slug, node,
            "raw .lower().compile() chain bypasses the compile-artifact "
            "cache tier: route it through utils/compile_cache.aot_compile "
            "(manifest-first, zero compiles on a warm restart) or "
            "suppress with justification for one-shot host tooling")


# ----------------------------------------------------------------------
# R4: impure jit bodies
# ----------------------------------------------------------------------

@register
class ImpureJitRule(Rule):
    name = "R4"
    slug = "impure-jit"
    description = (
        "impure call inside traced code (telemetry records, clocks, "
        "Python/numpy RNG, I/O): it fires at trace time only — or hides "
        "a sync; record device stats via the fetched-one-step-late "
        "pattern (telemetry.health / telemetry.scorepipe)")

    def check(self, mod: LintModule):
        facts = _facts(mod)
        for fn in facts.traced:
            for node in ast.walk(fn):
                if mod.enclosing_function(node) is not fn:
                    continue
                if not isinstance(node, ast.Call):
                    continue
                why = self._impure(node, mod)
                if why:
                    yield mod.finding(
                        self.name, self.slug, node,
                        f"{why} inside traced code runs at trace time "
                        "only (or forces a sync); hoist it to the host "
                        "loop / fetch one step late")

    @staticmethod
    def _impure(call, mod):
        f = call.func
        if isinstance(f, ast.Name) and f.id in _IMPURE_NAME_CALLS:
            return f"{f.id}()"
        dotted = mod.dotted(f)
        if dotted:
            if dotted.rsplit(".", 1)[-1] in _PURE_TELEMETRY:
                return None
            if (".telemetry.tracectx" in dotted
                    or dotted.startswith("tracectx.")):
                # trace contexts are telemetry-gated HOST bookkeeping —
                # fine in listener/host paths (R4 never looks there), but
                # inside traced code the contextvar read fires at trace
                # time only: attach()/handoff() around the jit call, never
                # inside it
                return (f"trace-context call {dotted} (host-side; "
                        "attach/handoff around the jit boundary)")
            if dotted.startswith("deeplearning4j_tpu.telemetry"):
                return f"telemetry call {dotted}"
            if dotted.startswith(_IMPURE_DOTTED_PREFIXES):
                return dotted
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
            root = f.value.id
            if root in _IMPURE_LOG_ROOTS:
                return f"{root}.{f.attr}()"
            if (f.attr in _IMPURE_METRIC_METHODS
                    and re.match(r"^_m_|^(reg|registry|frec|hm)$|_metric",
                                 root)):
                return f"metric/instrument call {root}.{f.attr}()"
        return None


# ----------------------------------------------------------------------
# R5: unguarded backend-specific calls
# ----------------------------------------------------------------------

@register
class BackendGuardRule(Rule):
    name = "R5"
    slug = "backend-guard"
    description = (
        "backend-specific call (memory_stats/live_arrays/...) outside a "
        "try/except guard: CPU backends return None or raise — the "
        "telemetry.devices poll idiom wraps every such call")

    def check(self, mod: LintModule):
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _BACKEND_CALLS):
                continue
            if any(isinstance(a, ast.Try) for a in mod.ancestors(node)):
                continue
            yield mod.finding(
                self.name, self.slug, node,
                f".{node.func.attr}() is backend-specific (absent/None on "
                "CPU); wrap in try/except or gate on the platform")


# ----------------------------------------------------------------------
# R6: concurrency smells
# ----------------------------------------------------------------------

@register
class ThreadDisciplineRule(Rule):
    name = "R6"
    slug = "thread-discipline"
    description = (
        "concurrency smells in thread-using modules: threading.Thread "
        "without an explicit daemon flag; read-modify-write of a shared "
        "self attribute outside the owning lock in a lock-bearing class")

    def check(self, mod: LintModule):
        if "threading" not in mod.aliases.values() \
                and "threading" not in mod.aliases:
            return
        for node in ast.walk(mod.tree):
            if (isinstance(node, ast.Call)
                    and mod.dotted(node.func) == "threading.Thread"
                    and not any(k.arg == "daemon" for k in node.keywords)):
                yield mod.finding(
                    self.name, self.slug, node,
                    "threading.Thread without an explicit daemon= — state "
                    "the join/daemon discipline at construction")
        for cls in (n for n in ast.walk(mod.tree)
                    if isinstance(n, ast.ClassDef)):
            locks = self._lock_attrs(cls, mod)
            if not locks:
                continue
            yield from self._unlocked_writes(cls, locks, mod)

    @staticmethod
    def _lock_attrs(cls, mod):
        names = set()
        for node in ast.walk(cls):
            if not isinstance(node, ast.Assign):
                continue
            if not (isinstance(node.value, ast.Call) and mod.dotted(
                    node.value.func) in ("threading.Lock",
                                         "threading.RLock",
                                         "threading.Condition")):
                continue
            for t in node.targets:
                if (isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"):
                    names.add(t.attr)
        return names

    def _unlocked_writes(self, cls, locks, mod):
        for fn in (n for n in ast.walk(cls)
                   if isinstance(n, (ast.FunctionDef,
                                     ast.AsyncFunctionDef))):
            if fn.name == "__init__":
                continue  # construction is single-threaded
            for node in ast.walk(fn):
                if mod.enclosing_function(node) is not fn:
                    continue
                attr = self._rmw_self_attr(node, mod)
                if attr is None or attr in locks:
                    continue
                if self._under_lock(node, locks, fn, mod):
                    continue
                yield mod.finding(
                    self.name, self.slug, node,
                    f"read-modify-write of shared self.{attr} outside "
                    f"the owning lock (class holds "
                    f"{', '.join(sorted('self.' + l for l in locks))})")

    @staticmethod
    def _rmw_self_attr(node, mod):
        """self attribute mutated non-atomically by this node, or None."""
        def root_self_attr(t):
            while isinstance(t, ast.Subscript):
                t = t.value
            if (isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"):
                return t.attr
            return None

        if isinstance(node, ast.AugAssign):
            return root_self_attr(node.target)
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _MUTATING_METHODS):
            return root_self_attr(node.func.value)
        return None

    @staticmethod
    def _under_lock(node, locks, fn, mod):
        for a in mod.ancestors(node):
            if a is fn:
                return False
            if isinstance(a, ast.With):
                for item in a.items:
                    ctx = item.context_expr
                    if isinstance(ctx, ast.Call):
                        ctx = ctx.func
                    if (isinstance(ctx, ast.Attribute)
                            and isinstance(ctx.value, ast.Name)
                            and ctx.value.id == "self"
                            and ctx.attr in locks):
                        return True
        return False
