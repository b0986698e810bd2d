"""AST trace-safety lint (layer 1 of :mod:`repro.analysis`).

Each rule is keyed to a bug class this repo has actually shipped:

``JL101 host-op-on-tracer``
    ``np.*`` / ``float()`` / ``int()`` / ``bool()`` / ``.item()`` /
    ``.tolist()`` applied to a traced value inside jitted scope.  PR 3
    shipped exactly this (``np.asarray``-on-tracer rounding inside
    ``quantize_blockwise``); on the device path it either crashes under
    jit or silently forces a host sync per call.

``JL102 traced-control-flow``
    Python ``if``/``while`` branching on a traced value.  Under jit the
    branch is resolved once at trace time with whatever concrete value
    the tracer happened to abstract — i.e. it measures the first call,
    forever.

``JL103 captured-attr-mutation``
    Assigning ``self.<attr>`` outside ``__init__`` when ``<attr>`` is
    read by a function wrapped in a cached executable (``jax.jit``).
    The executable baked the old value in at trace time, so the
    mutation is silently ignored — the PR-4 ``temperature``/``top_k``
    class.

``JL104 wall-clock-in-trace``
    ``time.*`` / ``random.*`` / ``np.random.*`` / ``datetime.*`` calls
    in traced scope: evaluated once at trace time, constant thereafter.
    Timing *inside* a jitted region also measures nothing (dispatch is
    async) — timed regions belong outside, around ``block_until_ready``.

``JL105 stale-memo-cache``
    ``functools.lru_cache``/``cache`` on a function whose value depends
    on a mutable registry (the PR-3 ``_format_table`` class: memoized
    over ``dtype_registry()`` output, stale after plugin registration).

Suppression: an inline ``# jaxlint: disable=RULE(reason)`` pragma on
the finding line (or the line above, or the enclosing ``def``), or a
committed baseline (``tools/jaxlint_baseline.json``) so the gate starts
green; baseline entries match on (path, rule, scope, source text), so
they age out when the code they waived changes.

The linter is deliberately repo-shaped: ``DEFAULT_TRACED_ROOTS`` names
the hot entry points (``lm_decode_step``, ``quantize_blockwise``, the
Pallas kernels, ...) that are jitted *by callers in other modules*, and
tracedness propagates transitively through the intra-module call graph.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

RULES: Dict[str, str] = {
    "JL101": "host-op-on-tracer",
    "JL102": "traced-control-flow",
    "JL103": "captured-attr-mutation",
    "JL104": "wall-clock-in-trace",
    "JL105": "stale-memo-cache",
    # layer 2 (repro.analysis.contracts)
    "CT301": "packed-upcast",
    "CT302": "host-callback",
    "CT303": "cache-width",
    # layer 3 (repro.analysis.pallas_check)
    "PC200": "uncovered-site",
    "PC201": "write-race",
    "PC202": "unsound-alias",
    "PC203": "vmem-overflow",
}
_NAME_TO_ID = {v: k for k, v in RULES.items()}

# Attribute reads that are static at trace time (safe to branch on).
STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "format",
                "aval", "weak_type", "itemsize", "nbytes"}

# np.* calls that only inspect type/metadata, never force the value.
NP_SAFE_FUNCS = {"isscalar", "dtype", "shape", "ndim", "result_type",
                 "issubdtype", "can_cast", "promote_types", "iinfo",
                 "finfo", "prod", "dtype_of"}

# Builtin predicates whose result is static for tracers.
STATIC_PREDICATES = {"isinstance", "issubclass", "hasattr", "callable",
                     "len", "type", "id", "repr", "str"}

# Parameter names that by repo convention hold static config, not arrays.
STATIC_PARAM_NAMES = {
    "self", "cls", "cfg", "config", "fmt", "kv_format", "weight_format",
    "name", "mode", "axis", "interpret", "dtype", "out_dtype",
    "compute_dtype", "spec", "pattern", "path", "fn", "model", "key_fn",
}

_HOST_CONVERTERS = {"float", "int", "bool", "complex"}
_FORCING_METHODS = {"item", "tolist", "__array__"}

_CLOCK_MODULES = {
    ("time",): "time.* is evaluated once at trace time",
    ("random",): "stdlib random runs at trace time (constant under jit)",
    ("np", "random"): "np.random runs at trace time; use jax.random",
    ("numpy", "random"): "np.random runs at trace time; use jax.random",
    ("datetime",): "datetime.* is evaluated once at trace time",
}

# Entry points jitted by callers outside their own module.  Keys are
# path suffixes, values the function names to treat as traced roots.
DEFAULT_TRACED_ROOTS: Dict[str, Set[str]] = {
    "models/transformer.py": {
        "lm_decode_step", "lm_prefill_chunk", "lm_prefill", "lm_forward",
        "lm_features", "lm_encode_slot", "clear_slot", "kv_cache_stats",
    },
    "models/attention.py": {
        "decode_attention", "cache_attention", "cache_kv", "quantize_kv",
        "dequantize_kv",
    },
    "models/slotstate.py": {
        "mask_rows", "masked_tree", "decode_advance", "take_layer",
        "put_layer", "take_slot", "put_slot", "take_row", "put_row",
        "clear_slot",
    },
    "models/ssm.py": {"ssm_prefill_chunk"},
    "serve/quant.py": {"quantize_blockwise", "dequantize_blockwise"},
    "serve/sampler.py": {"sample_token", "sample_tokens",
                         "fold_slot_keys"},
    "serve/faults.py": {"overflow_e8m0_scales", "flip_kv_bytes",
                        "poison_recurrent_state"},
    "repro/lowbits.py": {
        "decode", "quantize_values", "encode_codes", "unpack_codes",
        "e8m0_decode", "e8m0_scale_code",
    },
}


@dataclasses.dataclass
class Finding:
    path: str
    line: int
    col: int
    rule: str
    message: str
    context: str = ""          # qualified name of the enclosing scope
    text: str = ""             # stripped source line

    @property
    def rule_name(self) -> str:
        return RULES.get(self.rule, "?")

    def fingerprint(self) -> Tuple[str, str, str, str]:
        return (self.path, self.rule, self.context, self.text)

    def render(self) -> str:
        ctx = f" [{self.context}]" if self.context else ""
        return (f"{self.path}:{self.line}:{self.col}: "
                f"{self.rule}/{self.rule_name}{ctx}: {self.message}")


@dataclasses.dataclass
class LintConfig:
    traced_roots: Dict[str, Set[str]] = dataclasses.field(
        default_factory=lambda: {k: set(v) for k, v in
                                 DEFAULT_TRACED_ROOTS.items()})
    select: Optional[Set[str]] = None     # restrict to these rule ids


# ---------------------------------------------------------------------------
# pragma parsing


_PRAGMA_RE = re.compile(r"#\s*jaxlint:\s*disable=([^#]*)")
_PRAGMA_ITEM_RE = re.compile(r"(JL\d{3}|[a-z][a-z0-9-]+)\s*(?:\(([^)]*)\))?")


def _parse_pragmas(source: str) -> Dict[int, Set[str]]:
    """line number -> set of disabled rule ids (names normalised)."""
    out: Dict[int, Set[str]] = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = _PRAGMA_RE.search(line)
        if not m:
            continue
        rules: Set[str] = set()
        for item in _PRAGMA_ITEM_RE.finditer(m.group(1)):
            rid = item.group(1)
            rules.add(_NAME_TO_ID.get(rid, rid))
        if rules:
            out[i] = rules
    return out


# ---------------------------------------------------------------------------
# small AST helpers


def _attr_chain(node: ast.AST) -> Optional[Tuple[str, ...]]:
    """``a.b.c`` -> ("a", "b", "c"); None for non-trivial bases."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return None


def _is_jax_jit(node: ast.AST) -> bool:
    chain = _attr_chain(node)
    return chain in (("jax", "jit"), ("jit",), ("jax", "pmap"),
                     ("pjit",), ("jax", "experimental", "pjit", "pjit"))


def _is_partial(node: ast.AST) -> bool:
    return _attr_chain(node) in (("functools", "partial"), ("partial",))


def _is_memoizer(node: ast.AST) -> bool:
    chain = _attr_chain(node)
    if chain is None:
        return False
    return chain in (("functools", "lru_cache"), ("lru_cache",),
                     ("functools", "cache"), ("cache",))


def _const_str_tuple(node: ast.AST) -> Set[str]:
    """Extract constant strings from a str / tuple-of-str node."""
    out: Set[str] = set()
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        out.add(node.value)
    elif isinstance(node, (ast.Tuple, ast.List)):
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                out.add(elt.value)
    return out


def _jit_static_argnames(call: ast.Call) -> Set[str]:
    names: Set[str] = set()
    for kw in call.keywords:
        if kw.arg in ("static_argnames", "static_argnums"):
            names |= _const_str_tuple(kw.value)
    return names


_MARKER_CALLS = {
    # callee chain suffix -> positional indices holding traced callables
    ("scan",): (0,),
    ("fori_loop",): (2,),
    ("while_loop",): (0, 1),
    ("cond",): (1, 2),
    ("switch",): (1,),
    ("vmap",): (0,),
    ("grad",): (0,),
    ("value_and_grad",): (0,),
    ("checkpoint",): (0,),
    ("remat",): (0,),
    ("pallas_call",): (0,),
    ("custom_vjp",): (0,),
    ("custom_jvp",): (0,),
    ("associative_scan",): (0,),
    ("lax", "map"): (0,),   # jax.lax.map only — NOT jax.tree.map
}


class _FuncRecord:
    __slots__ = ("node", "qualname", "traced", "static_params",
                 "class_name", "calls", "reason")

    def __init__(self, node, qualname, class_name):
        self.node = node
        self.qualname = qualname
        self.class_name = class_name
        self.traced = False
        self.reason = ""
        self.static_params: Set[str] = set()
        self.calls: Set[str] = set()     # simple names called in body


class _ModuleIndex(ast.NodeVisitor):
    """Collect functions, trace markers, and the intra-module call graph."""

    def __init__(self):
        self.funcs: Dict[str, _FuncRecord] = {}   # qualname -> record
        self.by_name: Dict[str, List[_FuncRecord]] = {}
        self._stack: List[str] = []
        self._class: List[str] = []
        # names referenced as callables in traced-marker positions
        self.marked_names: Set[str] = set()
        # (class, method) pairs marked via jax.jit(self.method)
        self.marked_methods: Set[Tuple[str, str]] = set()
        # qualnames of functions that *call* jax.jit / markers, with the
        # jit call node (needed for JL103 capture analysis)
        self.jit_sites: List[Tuple[str, Optional[str], ast.Call]] = []
        self.memoized: List[_FuncRecord] = []
        self._alias: List[Dict[str, str]] = [dict()]

    # -- scope bookkeeping ----------------------------------------------
    def _qual(self, name: str) -> str:
        return ".".join(self._stack + [name]) if self._stack else name

    def visit_ClassDef(self, node: ast.ClassDef):
        self._stack.append(node.name)
        self._class.append(node.name)
        self.generic_visit(node)
        self._class.pop()
        self._stack.pop()

    def _visit_func(self, node):
        qual = self._qual(node.name)
        rec = _FuncRecord(node, qual,
                          self._class[-1] if self._class else None)
        # decorators
        for dec in node.decorator_list:
            if _is_jax_jit(dec) or _attr_chain(dec) in (
                    ("jax", "vmap"), ("jax", "checkpoint"),
                    ("jax", "remat"), ("jax", "custom_vjp"),
                    ("jax", "custom_jvp")):
                rec.traced = True
                rec.reason = "jit-decorated"
            elif isinstance(dec, ast.Call):
                if _is_jax_jit(dec.func):
                    rec.traced = True
                    rec.reason = "jit-decorated"
                    rec.static_params |= _jit_static_argnames(dec)
                elif _is_partial(dec.func) and dec.args and \
                        _is_jax_jit(dec.args[0]):
                    rec.traced = True
                    rec.reason = "jit-decorated"
                    rec.static_params |= _jit_static_argnames(dec)
                elif _is_memoizer(dec.func):
                    self.memoized.append(rec)
            elif _is_memoizer(dec):
                self.memoized.append(rec)
        self.funcs[qual] = rec
        self.by_name.setdefault(node.name, []).append(rec)
        self._stack.append(node.name)
        self._alias.append(dict())
        self.generic_visit(node)
        self._alias.pop()
        self._stack.pop()

    def visit_FunctionDef(self, node):
        self._visit_func(node)

    def visit_AsyncFunctionDef(self, node):
        self._visit_func(node)

    # -- marker / alias / call-graph collection -------------------------
    def _resolve_alias(self, name: str) -> str:
        for frame in reversed(self._alias):
            if name in frame:
                return frame[name]
        return name

    def _mark_callable_arg(self, arg: ast.AST):
        if isinstance(arg, ast.Name):
            self.marked_names.add(self._resolve_alias(arg.id))
        elif isinstance(arg, ast.Attribute):
            chain = _attr_chain(arg)
            if chain and chain[0] == "self" and len(chain) == 2:
                cls = self._class[-1] if self._class else None
                if cls:
                    self.marked_methods.add((cls, chain[1]))
        elif isinstance(arg, ast.Lambda):
            # lambdas in traced positions: handled by the outer scope
            # being traced (their bodies are visited as expressions of
            # the enclosing function), nothing extra to record.
            pass
        elif isinstance(arg, ast.Call) and _is_partial(arg.func) and arg.args:
            self._mark_callable_arg(arg.args[0])

    def visit_Assign(self, node: ast.Assign):
        # track `k = functools.partial(f, ...)` and `g = f` aliases
        if len(node.targets) == 1 and isinstance(node.targets[0], ast.Name):
            tgt = node.targets[0].id
            val = node.value
            if isinstance(val, ast.Call) and _is_partial(val.func) \
                    and val.args and isinstance(val.args[0], ast.Name):
                self._alias[-1][tgt] = val.args[0].id
            elif isinstance(val, ast.Name):
                self._alias[-1][tgt] = self._resolve_alias(val.id)
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call):
        chain = _attr_chain(node.func)
        enclosing = ".".join(self._stack) if self._stack else None
        if chain:
            if chain in (("jax", "jit"), ("jit",)) or \
                    (_is_partial(node.func) and node.args and
                     _is_jax_jit(node.args[0])):
                args = node.args
                if _is_partial(node.func):
                    args = node.args[1:]
                for a in args[:1]:
                    self._mark_callable_arg(a)
                self.jit_sites.append(
                    (enclosing or "<module>",
                     self._class[-1] if self._class else None, node))
            else:
                for suffix, positions in _MARKER_CALLS.items():
                    if chain[-len(suffix):] == suffix:
                        for p in positions:
                            if p < len(node.args):
                                self._mark_callable_arg(node.args[p])
                        break
            if len(chain) == 1 and enclosing is not None:
                cur = self.funcs.get(enclosing)
                if cur is not None:
                    cur.calls.add(self._resolve_alias(chain[0]))
        self.generic_visit(node)


# ---------------------------------------------------------------------------
# taint / rule checking inside a traced function


class _ExprScan(ast.NodeVisitor):
    """Collect Name references in an expression, skipping subtrees that
    are static at trace time (``x.shape``, ``isinstance(x, ...)``,
    ``x is None``)."""

    def __init__(self):
        self.names: Set[str] = set()

    def visit_Attribute(self, node: ast.Attribute):
        if node.attr in STATIC_ATTRS:
            return
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call):
        chain = _attr_chain(node.func)
        if chain is not None:
            if chain[-1] in STATIC_PREDICATES and len(chain) == 1:
                return
            if chain[0] in ("np", "numpy") and chain[-1] in NP_SAFE_FUNCS:
                return
            # is_quantized_cache(...), has_*/supports_* — structure
            # predicates, resolved at trace time by repo convention
            if chain[-1].startswith(("is_", "has_", "supports_")):
                return
        self.generic_visit(node)

    def visit_Compare(self, node: ast.Compare):
        # `x is None`, `"k_q" in cache`: identity and container
        # membership are static at trace time
        if all(isinstance(op, (ast.Is, ast.IsNot, ast.In, ast.NotIn))
               for op in node.ops):
            return
        self.generic_visit(node)

    def visit_Name(self, node: ast.Name):
        self.names.add(node.id)


def _dynamic_names(expr: ast.AST) -> Set[str]:
    scan = _ExprScan()
    scan.visit(expr)
    return scan.names


def _all_names(expr: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(expr) if isinstance(n, ast.Name)}


def _target_names(target: ast.AST) -> Set[str]:
    out: Set[str] = set()
    for n in ast.walk(target):
        if isinstance(n, ast.Name):
            out.add(n.id)
    return out


class _TracedChecker(ast.NodeVisitor):
    """Run JL101/JL102/JL104 over one traced function body."""

    def __init__(self, rec: _FuncRecord, path: str, lines: List[str],
                 findings: List[Finding], inherited: Set[str]):
        self.rec = rec
        self.path = path
        self.lines = lines
        self.findings = findings
        self.tainted: Set[str] = set(inherited)
        node = rec.node
        args = node.args
        for a in list(args.posonlyargs) + list(args.args):
            if a.arg in STATIC_PARAM_NAMES or \
                    a.arg in rec.static_params or _static_annotation(a):
                continue
            self.tainted.add(a.arg)
        # keyword-only params are bound via functools.partial in this
        # repo's kernel idiom (block sizes, flags) — treat as static
        # unless they look like arrays.
        for a in args.kwonlyargs:
            if a.arg in ("q", "k", "v", "x", "w", "acc"):
                self.tainted.add(a.arg)
        if args.vararg:
            self.tainted.add(args.vararg.arg)

    # -- helpers --------------------------------------------------------
    def _emit(self, node: ast.AST, rule: str, msg: str):
        line = getattr(node, "lineno", 0)
        text = self.lines[line - 1].strip() if 0 < line <= len(self.lines) \
            else ""
        self.findings.append(Finding(
            path=self.path, line=line,
            col=getattr(node, "col_offset", 0) + 1, rule=rule,
            message=msg, context=self.rec.qualname, text=text))

    def _is_tainted_expr(self, expr: ast.AST) -> bool:
        return bool(_dynamic_names(expr) & self.tainted)

    def _rhs_taints(self, value: ast.AST) -> bool:
        if self._is_tainted_expr(value):
            return True
        for call in ast.walk(value):
            if isinstance(call, ast.Call):
                chain = _attr_chain(call.func)
                if chain and chain[0] in ("jnp", "jax", "lax", "pl",
                                          "plgpu", "pltpu"):
                    return True
        return False

    # -- taint propagation ---------------------------------------------
    def visit_Assign(self, node: ast.Assign):
        self.generic_visit(node)
        if self._rhs_taints(node.value):
            for t in node.targets:
                self.tainted |= _target_names(t)

    def visit_AnnAssign(self, node: ast.AnnAssign):
        self.generic_visit(node)
        if node.value is not None and self._rhs_taints(node.value):
            self.tainted |= _target_names(node.target)

    def visit_AugAssign(self, node: ast.AugAssign):
        self.generic_visit(node)
        if self._rhs_taints(node.value):
            self.tainted |= _target_names(node.target)

    def visit_FunctionDef(self, node):
        # nested defs are checked separately with inherited taint
        pass

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_ClassDef(self, node):
        pass

    # -- JL102 ----------------------------------------------------------
    def visit_If(self, node: ast.If):
        if self._is_tainted_expr(node.test):
            names = sorted(_dynamic_names(node.test) & self.tainted)
            self._emit(node, "JL102",
                       f"Python `if` on traced value(s) {names}: the "
                       "branch is resolved once at trace time; use "
                       "jnp.where / lax.cond")
        self.generic_visit(node)

    def visit_While(self, node: ast.While):
        if self._is_tainted_expr(node.test):
            names = sorted(_dynamic_names(node.test) & self.tainted)
            self._emit(node, "JL102",
                       f"Python `while` on traced value(s) {names}: "
                       "use lax.while_loop")
        self.generic_visit(node)

    def visit_Assert(self, node: ast.Assert):
        if self._is_tainted_expr(node.test):
            names = sorted(_dynamic_names(node.test) & self.tainted)
            self._emit(node, "JL102",
                       f"`assert` on traced value(s) {names}: resolved "
                       "at trace time (checks nothing at runtime)")
        self.generic_visit(node)

    # -- JL101 / JL104 ---------------------------------------------------
    def visit_Call(self, node: ast.Call):
        chain = _attr_chain(node.func)
        arg_tainted = any(self._is_tainted_expr(a) for a in node.args) or \
            any(kw.value is not None and self._is_tainted_expr(kw.value)
                for kw in node.keywords)
        if chain is not None:
            root, leaf = chain[0], chain[-1]
            if root in ("np", "numpy") and len(chain) > 1 \
                    and leaf not in NP_SAFE_FUNCS and arg_tainted:
                self._emit(node, "JL101",
                           f"`{'.'.join(chain)}` on a traced value: "
                           "forces a host sync / breaks under jit; use "
                           "the jnp equivalent")
            elif chain in (("float",), ("int",), ("bool",), ("complex",)) \
                    and arg_tainted:
                self._emit(node, "JL101",
                           f"`{leaf}()` on a traced value forces a "
                           "device sync; keep it as a device scalar")
            else:
                for prefix, why in _CLOCK_MODULES.items():
                    if chain[:len(prefix)] == prefix and \
                            len(chain) > len(prefix):
                        self._emit(node, "JL104",
                                   f"`{'.'.join(chain)}` in traced "
                                   f"scope: {why}")
                        break
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr in _FORCING_METHODS and \
                self._is_tainted_expr(node.func.value):
            self._emit(node, "JL101",
                       f"`.{node.func.attr}()` on a traced value "
                       "forces a device sync")
        self.generic_visit(node)


def _static_annotation(arg: ast.arg) -> bool:
    ann = arg.annotation
    if ann is None:
        return False
    if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
        name = ann.value
    else:
        chain = _attr_chain(ann)
        name = chain[-1] if chain else ""
    # Python-scalar annotations are static by repo convention: traced
    # values are annotated `jax.Array`; `int`/`float` params are shapes,
    # block sizes, and sampling knobs baked in at trace time.
    return name in {"str", "bool", "int", "float", "Config",
                    "ArchConfig", "ModelConfig", "BlockSpec",
                    "PackedSpec", "Callable", "Model"}


# ---------------------------------------------------------------------------
# JL103: mutation of jit-captured attributes


def _self_attr_reads(node: ast.AST) -> Set[str]:
    return {sub.attr for sub in ast.walk(node)
            if isinstance(sub, ast.Attribute)
            and isinstance(sub.ctx, ast.Load)
            and isinstance(sub.value, ast.Name)
            and sub.value.id == "self"
            and sub.attr not in STATIC_ATTRS}


def _local_attr_flow(method: ast.AST) -> Dict[str, Set[str]]:
    """local name -> self attrs whose values flowed into it, e.g.
    ``temp, top_k = self.temperature, self.top_k`` (the PR-4 shape)."""
    flow: Dict[str, Set[str]] = {}
    for stmt in ast.walk(method):
        if not isinstance(stmt, ast.Assign):
            continue
        attrs = _self_attr_reads(stmt.value)
        if not attrs:
            continue
        # pairwise-map tuple assignments when arities line up
        if len(stmt.targets) == 1 and \
                isinstance(stmt.targets[0], ast.Tuple) and \
                isinstance(stmt.value, ast.Tuple) and \
                len(stmt.targets[0].elts) == len(stmt.value.elts):
            for tgt, val in zip(stmt.targets[0].elts, stmt.value.elts):
                if isinstance(tgt, ast.Name):
                    a = _self_attr_reads(val)
                    if a:
                        flow.setdefault(tgt.id, set()).update(a)
            continue
        for t in stmt.targets:
            for name in _target_names(t):
                flow.setdefault(name, set()).update(attrs)
    return flow


def _check_captured_mutation(tree: ast.Module, path: str,
                             lines: List[str], findings: List[Finding]):
    for cls in [n for n in ast.walk(tree) if isinstance(n, ast.ClassDef)]:
        methods = {n.name: n for n in cls.body
                   if isinstance(n, (ast.FunctionDef,
                                     ast.AsyncFunctionDef))}
        captured: Dict[str, str] = {}   # attr -> where it was captured

        def note(attrs: Set[str], where: str):
            for a in attrs:
                captured.setdefault(a, where)

        for name, m in methods.items():
            where = f"{cls.name}.{name}"
            flow = _local_attr_flow(m)
            local_defs = {n.name: n for n in ast.walk(m)
                          if isinstance(n, ast.FunctionDef) and n is not m}
            for call in ast.walk(m):
                if not isinstance(call, ast.Call):
                    continue
                if not (_is_jax_jit(call.func) or
                        (_is_partial(call.func) and call.args and
                         _is_jax_jit(call.args[0]))):
                    continue
                args = call.args[1:] if _is_partial(call.func) \
                    else call.args
                for a in args[:1]:
                    body: Optional[ast.AST] = None
                    site = where
                    chain = _attr_chain(a)
                    if isinstance(a, ast.Lambda):
                        body = a
                    elif isinstance(a, ast.Name) and a.id in local_defs:
                        body = local_defs[a.id]
                    elif chain and chain[0] == "self" and \
                            len(chain) == 2 and chain[1] in methods:
                        body = methods[chain[1]]
                        site = f"{cls.name}.{chain[1]}"
                    if body is None:
                        continue
                    # direct self.* reads in the jitted callable, plus
                    # self attrs that flowed into locals it closes over
                    attrs = set(_self_attr_reads(body))
                    free = _all_names(body)
                    for local, srcs in flow.items():
                        if local in free:
                            attrs |= srcs
                    note(attrs, site)
        if not captured:
            continue
        # private backing fields of read-only properties are fine: the
        # property pattern is the sanctioned fix for this rule.
        props = {n.name for n in cls.body
                 if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                 and any(_attr_chain(d) == ("property",)
                         for d in n.decorator_list)}
        for name, m in methods.items():
            if name == "__init__":
                continue
            is_setter = any(
                (c := _attr_chain(d)) and len(c) == 2 and c[1] == "setter"
                for d in m.decorator_list)
            for sub in ast.walk(m):
                if isinstance(sub, (ast.Assign, ast.AugAssign)):
                    targets = sub.targets if isinstance(sub, ast.Assign) \
                        else [sub.target]
                    for t in targets:
                        if isinstance(t, ast.Attribute) and \
                                isinstance(t.value, ast.Name) and \
                                t.value.id == "self" and \
                                t.attr in captured and not is_setter and \
                                t.attr.lstrip("_") not in props:
                            line = sub.lineno
                            text = lines[line - 1].strip() \
                                if line <= len(lines) else ""
                            findings.append(Finding(
                                path=path, line=line,
                                col=sub.col_offset + 1, rule="JL103",
                                message=(
                                    f"`self.{t.attr}` is captured by a "
                                    f"jitted executable (traced in "
                                    f"{captured[t.attr]}); mutating it "
                                    "here is silently ignored — rebuild "
                                    "the executable or make it a "
                                    "read-only property"),
                                context=f"{cls.name}.{name}", text=text))


# ---------------------------------------------------------------------------
# JL105: memo caches over mutable registry state


def _check_stale_memo(index: _ModuleIndex, path: str, lines: List[str],
                      findings: List[Finding]):
    for rec in index.memoized:
        own = rec.node.name
        for call in ast.walk(rec.node):
            if not isinstance(call, ast.Call):
                continue
            chain = _attr_chain(call.func)
            if chain is None:
                continue
            leaf = chain[-1]
            if leaf == own:
                continue
            if "registry" in leaf or leaf in ("get_registry",
                                              "registered_formats"):
                line = call.lineno
                text = lines[line - 1].strip() if line <= len(lines) else ""
                findings.append(Finding(
                    path=path, line=line, col=call.col_offset + 1,
                    rule="JL105",
                    message=(f"memoized `{own}` reads mutable registry "
                             f"state via `{'.'.join(chain)}`: the cache "
                             "goes stale after registration — key the "
                             "memo on the registry contents or drop it"),
                    context=rec.qualname, text=text))


# ---------------------------------------------------------------------------
# driver


def _mark_traced(index: _ModuleIndex, path: str, config: LintConfig):
    # roots from config (path-suffix match)
    for suffix, names in config.traced_roots.items():
        if path.endswith(suffix):
            for rec in index.funcs.values():
                if rec.node.name in names and not rec.traced:
                    rec.traced = True
                    rec.reason = "configured root"
    # names marked via jit()/scan()/pallas_call() call sites
    for rec in index.funcs.values():
        if rec.node.name in index.marked_names and not rec.traced:
            rec.traced = True
            rec.reason = "passed to a tracing transform"
        if rec.class_name and (rec.class_name, rec.node.name) in \
                index.marked_methods and not rec.traced:
            rec.traced = True
            rec.reason = "method passed to jax.jit"
    # nested defs inside traced functions are traced
    changed = True
    while changed:
        changed = False
        for qual, rec in index.funcs.items():
            if rec.traced:
                continue
            parent = qual.rsplit(".", 1)[0] if "." in qual else None
            if parent and parent in index.funcs and \
                    index.funcs[parent].traced and \
                    isinstance(index.funcs[parent].node,
                               (ast.FunctionDef, ast.AsyncFunctionDef)):
                rec.traced = True
                rec.reason = "nested in traced scope"
                changed = True
        # transitive: traced fn calls module-level fn by simple name
        for rec in index.funcs.values():
            if not rec.traced:
                continue
            for callee in rec.calls:
                for cand in index.by_name.get(callee, ()):  # same module
                    if not cand.traced and "." not in cand.qualname:
                        cand.traced = True
                        cand.reason = f"called from traced {rec.qualname}"
                        changed = True


def lint_source(source: str, path: str,
                config: Optional[LintConfig] = None) -> List[Finding]:
    config = config or LintConfig()
    try:
        tree = ast.parse(source)
    except SyntaxError as exc:
        return [Finding(path=path, line=exc.lineno or 0, col=0,
                        rule="JL100", message=f"syntax error: {exc.msg}")]
    lines = source.splitlines()
    index = _ModuleIndex()
    index.visit(tree)
    _mark_traced(index, path, config)

    findings: List[Finding] = []
    # inherited taint: names tainted in an enclosing traced function
    inherited: Dict[str, Set[str]] = {}
    for qual in sorted(index.funcs):   # parents sort before children
        rec = index.funcs[qual]
        if not rec.traced:
            continue
        parent = qual.rsplit(".", 1)[0] if "." in qual else None
        seed = inherited.get(parent, set()) if parent else set()
        checker = _TracedChecker(rec, path, lines, findings, seed)
        for stmt in rec.node.body:
            checker.visit(stmt)
        inherited[qual] = set(checker.tainted)

    _check_captured_mutation(tree, path, lines, findings)
    _check_stale_memo(index, path, lines, findings)

    # pragma suppression
    pragmas = _parse_pragmas(source)
    def_lines: Dict[str, int] = {q: r.node.lineno
                                 for q, r in index.funcs.items()}
    kept: List[Finding] = []
    for f in findings:
        if config.select and f.rule not in config.select:
            continue
        spots = [f.line, f.line - 1]
        if f.context in def_lines:
            spots.append(def_lines[f.context])
        if any(f.rule in pragmas.get(s, ()) for s in spots):
            continue
        kept.append(f)
    kept.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return kept


def lint_paths(paths: Sequence[str],
               config: Optional[LintConfig] = None,
               baseline: Optional[Iterable[dict]] = None,
               root: Optional[str] = None) -> List[Finding]:
    """Lint files/directories; drop findings matching the baseline."""
    import os

    config = config or LintConfig()
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = [d for d in dirnames
                               if d not in ("__pycache__", ".git")]
                files.extend(os.path.join(dirpath, f)
                             for f in sorted(filenames)
                             if f.endswith(".py"))
        elif p.endswith(".py"):
            files.append(p)
    findings: List[Finding] = []
    for fp in sorted(set(files)):
        with open(fp, "r", encoding="utf-8") as fh:
            source = fh.read()
        rel = os.path.relpath(fp, root) if root else fp
        findings.extend(lint_source(source, rel, config))
    if baseline:
        budget: Dict[Tuple[str, str, str, str], int] = {}
        for entry in baseline:
            key = (entry["path"], entry["rule"],
                   entry.get("context", ""), entry.get("text", ""))
            budget[key] = budget.get(key, 0) + 1
        kept = []
        for f in findings:
            key = f.fingerprint()
            if budget.get(key, 0) > 0:
                budget[key] -= 1
                continue
            kept.append(f)
        findings = kept
    return findings


def load_baseline(path: str) -> List[dict]:
    import os
    if not os.path.exists(path):
        return []
    with open(path, "r", encoding="utf-8") as fh:
        data = json.load(fh)
    return list(data.get("findings", []))


def write_baseline(path: str, findings: Sequence[Finding]) -> None:
    data = {
        "comment": ("jaxlint baseline: pre-existing findings waived at "
                    "gate introduction. Entries match on (path, rule, "
                    "scope, source text) and age out when the waived "
                    "line changes. Do not add new entries without a "
                    "review; prefer inline pragmas with reasons."),
        "findings": [
            {"path": f.path, "rule": f.rule, "context": f.context,
             "text": f.text, "message": f.message}
            for f in findings
        ],
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(data, fh, indent=2, sort_keys=False)
        fh.write("\n")
