"""graftlint hot-path checker: AST lint for the JAX device modules.

The headline claim (device-accelerated ``QC::verify``) lives on a JAX hot
path that degrades *silently*: a stray ``int(x)`` inside a jitted verify
program is a blocking host-device round trip per launch, a Python branch
on a traced value is a retrace (or a crash) per distinct input, a bare
float literal quietly promotes int32 limb math, and an undonated packed
buffer doubles device-memory pressure on the chip.  None of
these break a unit test — throughput just sags.  This pass finds them
mechanically.

Model: "hot" code is the jit closure — functions reachable from a jit /
pjit / shard_map / in-hot ``lax.scan`` root, following calls (including
across the scanned modules via ``from . import field25519 as F`` style
aliases) that pass at least one *tainted* (traced) argument.  Parameters
annotated as python scalars (``int``/``bool``/``str``/``bytes``) or with
literal defaults are static configuration, not traced values.  Taint is
laundered by static attributes (``.shape``/``.dtype``/``.ndim``/
``.size``) and ``len``, which is what keeps shape arithmetic legal.

Rules (see analysis/README.md):
  host-sync-in-jit     int()/float()/bool()/.item()/np.asarray() on a
                       traced value inside hot code
  traced-branch        if/while/assert/ternary on a traced value
  mutable-default-arg  dict/list/set default on a hot function parameter
  f64-literal          float literal meeting a traced value in hot code
                       (f64 promotion), or an explicit float64 dtype
  implicit-limb-dtype  jnp.array/np.array/jnp.asarray of a literal limb
                       list without an explicit dtype in hot code
  nondonated-buffer    jax.jit of a verify_* entry point without
                       donate_argnums (the verify loop hands each packed
                       buffer to the device exactly once)
"""

from __future__ import annotations

import ast
import os

from .common import Finding, apply_suppressions, parse_source, \
    read_source

# Paths scanned by default, relative to the repo root.
#
# crypto/eddsa.py and offchain/bls12381.py joined the set with the
# verifysched PR: eddsa is the dispatch layer the engine's hot loop calls
# straight into (its helpers are one refactor away from being pulled
# inside a jit closure — the cross-module taint walk keeps that honest),
# and bls12381 is the host BLS reference the device module's jit bodies
# call for constants/decoding, where a traced value leaking in would be
# a silent per-launch host sync.  sidecar/sched is control-plane code
# for the engine thread itself; scanning it keeps device-touching
# helpers from accreting there unchecked (lint_gate pins each module
# with --must-cover).
DEFAULT_TARGETS = (
    "hotstuff_tpu/ops",
    # graftkern: the ops/ scan is non-recursive (os.listdir), so the
    # Pallas kernel subpackage must be its own target — every kernel
    # body is jit-reachable device code where a stray host sync or an
    # implicit dtype is the exact silent-degradation class this scan
    # exists for (lint_gate pins each module with --must-cover).
    "hotstuff_tpu/ops/kern",
    "hotstuff_tpu/parallel",
    "hotstuff_tpu/sidecar/service.py",
    "hotstuff_tpu/sidecar/ring.py",
    "hotstuff_tpu/sidecar/sched",
    "hotstuff_tpu/crypto/eddsa.py",
    "hotstuff_tpu/offchain/bls12381.py",
)

_LAUNDER_ATTRS = {"shape", "dtype", "ndim", "size", "itemsize", "sharding"}
_STATIC_ANNOTATIONS = {"int", "bool", "str", "bytes", "float"}
_HOST_CASTS = {"int", "float", "bool"}
_UNTAINTED_CALLS = {"len", "range", "enumerate", "zip", "isinstance",
                    "type", "hasattr", "getattr", "divmod", "min", "max"}
_SCAN_HOFS = {("lax", "scan"), ("lax", "fori_loop"), ("lax", "while_loop"),
              ("lax", "map"), ("jax", "vmap"), ("jax", "pmap")}


def _attr_chain(node):
    """a.b.c -> ["a", "b", "c"]; None when the base isn't a plain name."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return list(reversed(parts))
    return None


class _Module:
    def __init__(self, path: str, source: str):
        self.path = path
        self.name = os.path.splitext(os.path.basename(path))[0]
        self.source = source
        self.tree = parse_source(source, path)
        self.functions: dict[str, ast.FunctionDef] = {}
        # alias -> module basename, for imports of *scanned* modules
        # (``from . import field25519 as F``, ``from ..ops import ed25519``)
        self.module_aliases: dict[str, str] = {}
        self.numpy_aliases: set[str] = set()
        self.jnp_aliases: set[str] = set()
        self.jax_aliases: set[str] = set()
        for node in self.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions[node.name] = node
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    alias = a.asname or a.name.split(".")[0]
                    if a.name == "numpy":
                        self.numpy_aliases.add(alias)
                    elif a.name == "jax.numpy":
                        self.jnp_aliases.add(a.asname or "jax")
                    elif a.name == "jax":
                        self.jax_aliases.add(alias)
            elif isinstance(node, ast.ImportFrom):
                for a in node.names:
                    alias = a.asname or a.name
                    if node.module == "jax" and a.name == "numpy":
                        self.jnp_aliases.add(alias)
                    elif node.module and node.module.endswith("numpy"):
                        self.numpy_aliases.add(alias)
                    else:
                        self.module_aliases[alias] = a.name


def _static_param_names(fn: ast.FunctionDef) -> set:
    """Parameters that are static python config, not traced arrays."""
    static = set()
    args = list(fn.args.posonlyargs) + list(fn.args.args) \
        + list(fn.args.kwonlyargs)
    for a in args:
        ann = a.annotation
        if isinstance(ann, ast.Name) and ann.id in _STATIC_ANNOTATIONS:
            static.add(a.arg)
    defaults = list(fn.args.defaults)
    # defaults align with the tail of posonly+args
    pos = list(fn.args.posonlyargs) + list(fn.args.args)
    for a, d in zip(pos[len(pos) - len(defaults):], defaults):
        if isinstance(d, (ast.Constant, ast.Tuple)):
            static.add(a.arg)
    for a, d in zip(fn.args.kwonlyargs, fn.args.kw_defaults):
        if isinstance(d, (ast.Constant, ast.Tuple)):
            static.add(a.arg)
    return static


def _param_names(fn: ast.FunctionDef) -> list:
    return [a.arg for a in list(fn.args.posonlyargs) + list(fn.args.args)
            + list(fn.args.kwonlyargs)] \
        + ([fn.args.vararg.arg] if fn.args.vararg else []) \
        + ([fn.args.kwarg.arg] if fn.args.kwarg else [])


class _FunctionPass(ast.NodeVisitor):
    """Taint walk over one hot function body."""

    def __init__(self, checker, module: _Module, fn, tainted: set):
        self.checker = checker
        self.module = module
        self.fn = fn
        self.tainted = set(tainted)
        self.local_defs = {}
        body = fn.body if not isinstance(fn, ast.Lambda) else [fn.body]
        for node in body:
            if isinstance(node, ast.FunctionDef):
                self.local_defs[node.name] = node

    # -- findings ----------------------------------------------------------

    def _report(self, node, rule, message):
        self.checker.report(self.module, node, rule, message)

    # -- taint evaluation --------------------------------------------------

    def is_tainted(self, node) -> bool:
        if node is None:
            return False
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in _LAUNDER_ATTRS:
                return False
            return self.is_tainted(node.value)
        if isinstance(node, ast.Subscript):
            return self.is_tainted(node.value)
        if isinstance(node, ast.Call):
            return self._eval_call(node)
        if isinstance(node, ast.BinOp):
            lt, rt = self.is_tainted(node.left), self.is_tainted(node.right)
            for side, other in ((node.left, rt), (node.right, lt)):
                if other and isinstance(side, ast.Constant) \
                        and isinstance(side.value, float):
                    self._report(
                        side, "f64-literal",
                        "bare float literal %r meets a traced value: "
                        "promotes integer limb math (f64 with x64 enabled); "
                        "use an explicitly-typed constant" % (side.value,))
            return lt or rt
        if isinstance(node, ast.BoolOp):
            return any(self.is_tainted(v) for v in node.values)
        if isinstance(node, ast.UnaryOp):
            return self.is_tainted(node.operand)
        if isinstance(node, ast.Compare):
            return self.is_tainted(node.left) or any(
                self.is_tainted(c) for c in node.comparators)
        if isinstance(node, ast.IfExp):
            if self.is_tainted(node.test):
                self._report(node, "traced-branch",
                             "ternary on a traced value inside jitted code "
                             "(concretization error or retrace); use "
                             "jnp.where / lax.select")
            return self.is_tainted(node.body) or self.is_tainted(node.orelse)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self.is_tainted(e) for e in node.elts)
        if isinstance(node, ast.Dict):
            return any(self.is_tainted(e)
                       for e in list(node.keys) + list(node.values) if e)
        if isinstance(node, ast.Starred):
            return self.is_tainted(node.value)
        if isinstance(node, ast.JoinedStr):
            return False
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp,
                             ast.DictComp)):
            return self._eval_comprehension(node)
        if isinstance(node, ast.Lambda):
            return False  # handled where it is passed to a scan HOF
        return False

    def _eval_comprehension(self, node) -> bool:
        saved = set(self.tainted)
        try:
            for gen in node.generators:
                if self.is_tainted(gen.iter):
                    self._taint_target(gen.target)
                for cond in gen.ifs:
                    if self.is_tainted(cond):
                        self._report(cond, "traced-branch",
                                     "comprehension filter on a traced "
                                     "value inside jitted code")
            if isinstance(node, ast.DictComp):
                return self.is_tainted(node.key) or \
                    self.is_tainted(node.value)
            return self.is_tainted(node.elt)
        finally:
            self.tainted = saved

    def _dtype_is_f64(self, node) -> bool:
        if isinstance(node, ast.Constant) and node.value in (
                "float64", "double"):
            return True
        chain = _attr_chain(node)
        return bool(chain) and chain[-1] == "float64"

    def _eval_call(self, node: ast.Call) -> bool:
        func = node.func
        args_tainted = [self.is_tainted(a) for a in node.args] + \
                       [self.is_tainted(k.value) for k in node.keywords]
        any_tainted = any(args_tainted)

        for kw in node.keywords:
            if kw.arg == "dtype" and self._dtype_is_f64(kw.value):
                self._report(kw.value, "f64-literal",
                             "explicit float64 dtype in hot code: the "
                             "device substrate is int32/f32 limb math")

        # x.item() — the canonical blocking device->host fetch
        if isinstance(func, ast.Attribute) and func.attr == "item" \
                and self.is_tainted(func.value):
            self._report(node, "host-sync-in-jit",
                         ".item() on a traced value: blocking host-device "
                         "sync inside jitted code")
            return False

        chain = _attr_chain(func)
        if chain:
            head, tail = chain[0], chain[-1]
            # int(x) / float(x) / bool(x) on a traced value
            if len(chain) == 1 and tail in _HOST_CASTS and any_tainted:
                self._report(node, "host-sync-in-jit",
                             "%s() on a traced value: forces a host "
                             "round trip (or a concretization error) "
                             "inside jitted code" % tail)
                return False
            if len(chain) == 1 and tail in _UNTAINTED_CALLS:
                return False
            # np.asarray / np.array of a device value
            if head in self.module.numpy_aliases and len(chain) == 2:
                if tail in ("asarray", "array") and any_tainted:
                    self._report(node, "host-sync-in-jit",
                                 "np.%s() of a traced value: copies the "
                                 "buffer to host inside jitted code" % tail)
                    return False
                if tail == "float64":
                    self._report(node, "f64-literal",
                                 "np.float64 in hot code promotes limb "
                                 "math to f64")
            # implicit-dtype array constants
            if tail in ("array", "asarray") and len(chain) == 2 and (
                    head in self.module.numpy_aliases
                    or head in self.module.jnp_aliases):
                if node.args and isinstance(node.args[0],
                                            (ast.List, ast.Tuple)) \
                        and not any(k.arg == "dtype"
                                    for k in node.keywords):
                    self._report(
                        node, "implicit-limb-dtype",
                        "%s.%s of a literal constant list without an "
                        "explicit dtype: relies on default promotion "
                        "(int32 vs int64/f64 differs across backends); "
                        "pass dtype=jnp.int32/uint32 explicitly"
                        % (head, tail))
            # scan-style higher-order fns: their body fn is hot with all
            # params tainted
            if len(chain) >= 2 and (chain[-2], tail) in _SCAN_HOFS \
                    and node.args:
                self._mark_callable_hot(node.args[0])
            if tail == "shard_map" and node.args:
                self._mark_callable_hot(node.args[0])

        # propagate into module-local / cross-module callees
        self._register_call(func, node, args_tainted)

        if isinstance(func, ast.Attribute):
            # method call on a tainted object (x.reshape(...), x.astype(..))
            if self.is_tainted(func.value):
                return True
        return any_tainted

    def _mark_callable_hot(self, arg):
        if isinstance(arg, ast.Lambda):
            sub = _FunctionPass(self.checker, self.module, arg,
                                {a.arg for a in arg.args.args})
            sub.is_tainted(arg.body)
            return
        if isinstance(arg, ast.Name):
            target = self.local_defs.get(arg.id) or \
                self.module.functions.get(arg.id)
            if target is not None:
                tainted = set(_param_names(target)) - \
                    _static_param_names(target)
                self.checker.analyze_local(self.module, target, tainted)

    def _register_call(self, func, node: ast.Call, args_tainted):
        """Taint the callee's parameters when a traced value flows in."""
        if not any(args_tainted):
            return
        target_module, target = None, None
        if isinstance(func, ast.Name):
            target = self.local_defs.get(func.id) or \
                self.module.functions.get(func.id)
            target_module = self.module
        elif isinstance(func, ast.Attribute) and \
                isinstance(func.value, ast.Name):
            mod_name = self.module.module_aliases.get(func.value.id)
            target_module = self.checker.modules_by_name.get(mod_name)
            if target_module is not None:
                target = target_module.functions.get(func.attr)
        if target is None or target_module is None:
            return
        params = _param_names(target)
        static = _static_param_names(target)
        tainted = set()
        for i, a in enumerate(node.args):
            if i < len(params) and args_tainted[i]:
                tainted.add(params[i])
        for kw, t in zip(node.keywords,
                         args_tainted[len(node.args):]):
            if kw.arg and t:
                tainted.add(kw.arg)
        tainted -= static
        if tainted:
            if target.name in target_module.functions:
                self.checker.enqueue(target_module, target.name, tainted)
            else:  # nested def: analyze inline
                self.checker.analyze_local(target_module, target, tainted)

    # -- statements --------------------------------------------------------

    def _taint_target(self, target):
        if isinstance(target, ast.Name):
            self.tainted.add(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self._taint_target(e)
        elif isinstance(target, ast.Starred):
            self._taint_target(target.value)

    def run(self):
        if isinstance(self.fn, ast.Lambda):
            self.is_tainted(self.fn.body)
            return
        # two passes so loop-carried assignments converge
        for _ in range(2):
            before = set(self.tainted)
            for stmt in self.fn.body:
                self.visit(stmt)
            if self.tainted == before:
                break

    def visit_FunctionDef(self, node):
        # nested defs are analyzed when they flow into a scan/shard_map or
        # are called with tainted args; check their defaults here
        self.checker.check_defaults(self.module, node, hot=False)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Assign(self, node):
        if self.is_tainted(node.value):
            for t in node.targets:
                self._taint_target(t)
        else:
            for t in node.targets:
                self.generic_untaint(t)

    def generic_untaint(self, target):
        if isinstance(target, ast.Name):
            self.tainted.discard(target.id)

    def visit_AnnAssign(self, node):
        if node.value is not None and self.is_tainted(node.value):
            self._taint_target(node.target)

    def visit_AugAssign(self, node):
        if self.is_tainted(node.value):
            self._taint_target(node.target)
        elif isinstance(node.target, ast.Name) and \
                node.target.id in self.tainted:
            # tainted op= untainted stays tainted; still check f64 meet
            if isinstance(node.value, ast.Constant) and \
                    isinstance(node.value.value, float):
                self._report(node.value, "f64-literal",
                             "bare float literal meets a traced value "
                             "(augmented assign)")

    def visit_If(self, node):
        if self.is_tainted(node.test):
            self._report(node, "traced-branch",
                         "python branch on a traced value inside jitted "
                         "code: concretization error or per-value retrace; "
                         "use jnp.where / lax.cond")
        for stmt in node.body + node.orelse:
            self.visit(stmt)

    def visit_While(self, node):
        if self.is_tainted(node.test):
            self._report(node, "traced-branch",
                         "while on a traced value inside jitted code; use "
                         "lax.while_loop")
        for stmt in node.body + node.orelse:
            self.visit(stmt)

    def visit_Assert(self, node):
        if self.is_tainted(node.test):
            self._report(node, "traced-branch",
                         "assert on a traced value inside jitted code "
                         "(concretization error); fold into the result "
                         "mask or use checkify")

    def visit_For(self, node):
        if self.is_tainted(node.iter):
            self._taint_target(node.target)
        for stmt in node.body + node.orelse:
            self.visit(stmt)

    def visit_Return(self, node):
        if node.value is not None:
            self.is_tainted(node.value)

    def visit_Expr(self, node):
        self.is_tainted(node.value)

    def visit_Try(self, node):
        # except-handler bodies are statements too — ast.ExceptHandler is
        # neither expr nor stmt, so the generic walk below would skip
        # them and hide violations in error paths.
        for stmt in node.body + node.orelse + node.finalbody:
            self.visit(stmt)
        for handler in node.handlers:
            for stmt in handler.body:
                self.visit(stmt)

    def generic_visit(self, node):
        # evaluate any expressions hanging off statements we don't model
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.expr):
                self.is_tainted(child)
            elif isinstance(child, ast.stmt):
                self.visit(child)


class HotPathChecker:
    def __init__(self, sources: dict):
        """sources: path -> python source text."""
        self.modules = {p: _Module(p, s) for p, s in sources.items()}
        self.modules_by_name = {m.name: m for m in self.modules.values()}
        self.findings: list[Finding] = []
        self._seen_findings: set = set()
        self._processed: dict = {}   # (module path, fn name) -> tainted set
        self._queue: list = []

    # -- reporting ---------------------------------------------------------

    def report(self, module: _Module, node, rule: str, message: str):
        key = (module.path, node.lineno, rule)
        if key in self._seen_findings:
            return
        self._seen_findings.add(key)
        self.findings.append(
            Finding(module.path, node.lineno, rule, message))

    def check_defaults(self, module: _Module, fn, hot: bool):
        if isinstance(fn, ast.Lambda):
            return
        if not hot:
            return
        for d in list(fn.args.defaults) + \
                [d for d in fn.args.kw_defaults if d is not None]:
            if isinstance(d, (ast.Dict, ast.List, ast.Set)):
                self.report(module, d, "mutable-default-arg",
                            "mutable default argument on a jit-reachable "
                            "function: unhashable as a static arg and a "
                            "retrace/aliasing hazard; default to None")

    # -- scheduling --------------------------------------------------------

    def enqueue(self, module: _Module, fn_name: str, tainted: set):
        key = (module.path, fn_name)
        already = self._processed.get(key, set())
        if tainted <= already:
            return
        self._processed[key] = already | tainted
        self._queue.append((module, module.functions[fn_name],
                            already | tainted))

    def analyze_local(self, module: _Module, fn, tainted: set):
        """Analyze a nested def / lambda right away (no global name)."""
        key = (module.path, id(fn))
        already = self._processed.get(key, set())
        if tainted <= already:
            return
        self._processed[key] = already | tainted
        self.check_defaults(module, fn, hot=True)
        _FunctionPass(self, module, fn, already | tainted).run()

    # -- roots -------------------------------------------------------------

    def _jit_roots(self, module: _Module):
        """Enqueue jit/pjit/shard_map roots with their traced params."""
        for fn in module.functions.values():
            for dec in fn.decorator_list:
                if self._is_jit_expr(module, dec):
                    static = self._static_argnames(dec, fn)
                    tainted = set(_param_names(fn)) - \
                        _static_param_names(fn) - static
                    self.enqueue(module, fn.name, tainted)
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func)
            if not chain:
                continue
            tail = chain[-1]
            if tail in ("jit", "pjit") and node.args:
                self._root_from_arg(module, node, node.args[0])
            elif tail == "shard_map" and node.args:
                self._root_from_arg(module, node, node.args[0])

    def _root_from_arg(self, module: _Module, call: ast.Call, arg):
        static = set()
        fn = None
        if isinstance(arg, ast.Name):
            fn = module.functions.get(arg.id)
        elif isinstance(arg, ast.Call) and isinstance(arg.func, ast.Name):
            # shard_map(make_body(...)) factory pattern: the factory's
            # nested defs are the hot bodies
            factory = module.functions.get(arg.func.id)
            if factory is not None:
                for stmt in ast.walk(factory):
                    if isinstance(stmt, ast.FunctionDef) and \
                            stmt is not factory:
                        tainted = set(_param_names(stmt)) - \
                            _static_param_names(stmt)
                        self.analyze_local(module, stmt, tainted)
            return
        if fn is None:
            return
        static = self._static_argnames(call, fn)
        tainted = set(_param_names(fn)) - _static_param_names(fn) - static
        self.enqueue(module, fn.name, tainted)

    def _is_jit_expr(self, module: _Module, node) -> bool:
        chain = _attr_chain(node)
        if chain and chain[-1] in ("jit", "pjit"):
            return True
        if isinstance(node, ast.Call):
            chain = _attr_chain(node.func)
            if chain and chain[-1] == "partial" and node.args:
                return self._is_jit_expr(module, node.args[0])
            if chain and chain[-1] in ("jit", "pjit"):
                return True
        return False

    @staticmethod
    def _static_argnames(call, fn) -> set:
        """Params excluded from tracing via static_argnums/static_argnames."""
        if not isinstance(call, ast.Call):
            return set()
        params = _param_names(fn)
        out = set()
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                vals = kw.value.elts if isinstance(
                    kw.value, (ast.Tuple, ast.List)) else [kw.value]
                for v in vals:
                    if isinstance(v, ast.Constant):
                        out.add(str(v.value))
            elif kw.arg == "static_argnums":
                vals = kw.value.elts if isinstance(
                    kw.value, (ast.Tuple, ast.List)) else [kw.value]
                for v in vals:
                    if isinstance(v, ast.Constant) and \
                            isinstance(v.value, int) and \
                            v.value < len(params):
                        out.add(params[v.value])
        return out

    # -- donation rule -----------------------------------------------------

    def _check_donation(self, module: _Module):
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func)
            if not chain or chain[-1] != "jit":
                continue
            if not (len(chain) == 1 or
                    chain[0] in module.jax_aliases):
                continue
            if not node.args:
                continue
            target = _attr_chain(node.args[0])
            if not target or not target[-1].startswith("verify"):
                continue
            kwargs = {kw.arg for kw in node.keywords}
            if None in kwargs or kwargs & {"donate_argnums",
                                           "donate_argnames"}:
                continue
            self.report(
                module, node, "nondonated-buffer",
                "jax.jit(%s) without donate_argnums: the verify loop "
                "hands each packed buffer to the device exactly once, so "
                "not donating it doubles device-memory pressure per "
                "launch; donate arg 0 (or suppress with a rationale if "
                "the caller re-times a device-resident input)"
                % target[-1])

    # -- driver ------------------------------------------------------------

    def run(self) -> list:
        for module in self.modules.values():
            self._check_donation(module)
            self._jit_roots(module)
        while self._queue:
            module, fn, tainted = self._queue.pop()
            self.check_defaults(module, fn, hot=True)
            _FunctionPass(self, module, fn, tainted).run()
        sources = {m.path: m.source for m in self.modules.values()}
        return sorted(apply_suppressions(self.findings, sources),
                      key=lambda f: (f.path, f.line))


def check_sources(sources: dict) -> list:
    """Lint a {path: source} mapping (the unit-test entry point)."""
    return HotPathChecker(sources).run()


def check(root: str, targets=DEFAULT_TARGETS) -> list:
    """Lint the repo's hot-path files under ``root``."""
    sources = {}
    for target in targets:
        path = os.path.join(root, target)
        if os.path.isfile(path):
            files = [path]
        elif os.path.isdir(path):
            files = sorted(
                os.path.join(path, f) for f in os.listdir(path)
                if f.endswith(".py"))
        else:
            continue
        for f in files:
            sources[os.path.relpath(f, root)] = read_source(f)
    return check_sources(sources)
