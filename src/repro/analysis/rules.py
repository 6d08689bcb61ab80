"""Determinism-contract AST lint rules (layer 1 of detlint).

Each rule encodes one way this codebase has actually lost (or nearly
lost) bitwise determinism:

DET001  raw ``jnp.sum/mean/cumsum`` / ``lax.psum`` (or ``.sum()``-style
        method reductions) in model/optim/train/serve/distributed code
        instead of the ``repro.reduce`` front door.  The front door is
        where policies, degrade handling, and the shard-merge contract
        live; a raw reduction silently opts out of all three.
DET002  Python-level float fold loops with no
        ``jax.lax.optimization_barrier`` in the body.  PR 8's tier-1
        catch: XLA fused two unrolled float folds into one reassociated
        add at S=1 — bitwise drift invisible at review time.
DET003  ``.at[...]`` scatter writes without an explicit ``mode=``.
        JAX's default drops out-of-bounds scatter indices *silently*
        (and negative indices wrap!); the mode must be a visible,
        reviewed decision at every write.
DET004  bare ``jax.random.split`` in per-request serving code.  Split
        chains depend on arrival order; the serving contract
        (docs/serving.md) requires order-free ``fold_in(seed, rid)``
        derivation.
DET005  registered ``Policy``/backend/``ReduceOp`` classes missing or
        mis-signaturing required hooks — checked against the *live*
        registries, so a hook rename that misses one policy fails here
        rather than deep inside a backend trace.
DET006  f32 count/index arithmetic: float32 represents integers exactly
        only up to 2^24, so counts accumulated in f32 saturate silently
        on large segments.

Waive a finding with ``# detlint: ok[DET00x] reason`` on (or above) the
offending line; ``tools/detlint.py --check-waivers`` ratchets the
per-rule waiver counts downward via ``tools/detlint_baseline.json``.
"""

from __future__ import annotations

import ast
import dataclasses
import re
from typing import Iterable, List, Optional, Sequence, Set

from repro.analysis import walker
from repro.analysis.walker import SourceModule, dotted_name

#: ``merge_is_add`` policies whose carry deliberately keeps float
#: leaves.  Entries here still count as waived findings in the ratchet
#: (rules DET005 here, DET102 in contracts) — the table is the pragma.
TOLERATED_FLOAT_MERGE = {
    "fast": ("documented-tolerance tier: psum of float partials is the "
             "policy's contract (docs/policies.md), not a determinism "
             "claim"),
}


@dataclasses.dataclass
class Finding:
    """One lint finding (waived or not)."""

    rule: str
    path: str
    line: int
    message: str
    waived: bool = False
    reason: str = ""

    def __str__(self) -> str:
        tag = " [waived]" if self.waived else ""
        return f"{self.path}:{self.line}: {self.rule}{tag} {self.message}"


def _in_dirs(mod: SourceModule, names: Set[str]) -> bool:
    return bool(set(mod.path.parts) & names)


class LintRule:
    """Base class: subclasses set ``rule``/``title`` and implement
    ``check(mod) -> iterable of (node, message)``."""

    rule = "DET000"
    title = ""

    def applies(self, mod: SourceModule) -> bool:
        return True

    def check(self, mod: SourceModule) -> Iterable:
        raise NotImplementedError

    def run(self, mod: SourceModule) -> List[Finding]:
        if not self.applies(mod):
            return []
        out = []
        for node, message in self.check(mod):
            w = mod.waiver_for(self.rule, node)
            out.append(Finding(rule=self.rule, path=mod.rel,
                               line=getattr(node, "lineno", 0),
                               message=message, waived=w is not None,
                               reason=w.reason if w else ""))
        return out


# ---------------------------------------------------------------------------
# DET001 — raw reductions outside the front door
# ---------------------------------------------------------------------------

#: layers that must route reductions through ``repro.reduce`` — the
#: front-door implementation itself (reduce/, kernels/, core/) is where
#: the raw primitives legitimately live.
_FRONT_DOOR_DIRS = {"models", "optim", "train", "serve", "distributed",
                    "launch", "data"}

_RAW_REDUCERS = {
    "jnp.sum", "jnp.mean", "jnp.cumsum", "jnp.nansum", "jnp.nanmean",
    "jax.numpy.sum", "jax.numpy.mean", "jax.numpy.cumsum",
    "lax.psum", "jax.lax.psum", "lax.pmean", "jax.lax.pmean",
}

_REDUCE_METHODS = {"sum", "mean", "cumsum"}
_MODULE_ROOTS = {"jnp", "jax", "lax", "np", "numpy", "math"}


class RawReduction(LintRule):
    rule = "DET001"
    title = "raw reduction outside the repro.reduce front door"

    def applies(self, mod: SourceModule) -> bool:
        return _in_dirs(mod, _FRONT_DOOR_DIRS)

    def check(self, mod: SourceModule):
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name in _RAW_REDUCERS:
                yield node, (f"raw `{name}` — route through the "
                             f"repro.reduce front door (policy + degrade "
                             f"+ shard-merge contract), or waive with the "
                             f"reason it must stay raw")
            elif (name is None or name.split(".")[0] not in _MODULE_ROOTS) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _REDUCE_METHODS:
                yield node, (f"method reduction `.{node.func.attr}()` — "
                             f"same contract as DET001 jnp.{node.func.attr}")


# ---------------------------------------------------------------------------
# DET002 — float fold loops without an optimization barrier
# ---------------------------------------------------------------------------

#: callee names that *are* fold steps when their result rebinds an input
_FOLD_CALLS = re.compile(r"(two_sum|wrap_add|limb_add|limb_merge|"
                         r"\bmerge\b|\bupdate\b)")

_JAX_ROOTS = {"jnp", "jax", "lax"}


def _contains_barrier(loop: ast.AST) -> bool:
    for n in ast.walk(loop):
        d = dotted_name(n) if isinstance(n, ast.Attribute) else None
        if d and d.endswith("optimization_barrier"):
            return True
    return False


_HOST_CASTS = {"float", "int", "len", "bool", "str"}


def _is_jaxish_expr(expr: ast.AST, jaxish_names: Set[str]) -> bool:
    """Heuristic: does this expression plausibly produce a traced array?
    True when it contains a call, a jnp/jax/lax-rooted attribute, or a
    name already known to hold a traced value."""
    # a top-level host cast (`t += float(...)`) produces a Python scalar:
    # whatever gets folded is host-side, not traced
    if isinstance(expr, ast.Call) and isinstance(expr.func, ast.Name) \
            and expr.func.id in _HOST_CASTS:
        return False
    for n in ast.walk(expr):
        if isinstance(n, ast.Call):
            return True
        if isinstance(n, ast.Attribute):
            d = dotted_name(n)
            if d and d.split(".")[0] in _JAX_ROOTS:
                return True
        if isinstance(n, ast.Name) and n.id in jaxish_names:
            return True
    return False


def _names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _mentions_jax(scope: ast.AST) -> bool:
    for n in ast.walk(scope):
        if isinstance(n, ast.Attribute):
            d = dotted_name(n)
            if d and d.split(".")[0] in _JAX_ROOTS:
                return True
    return False


def _direct_stmts(loop: ast.AST):
    """Statements of ``loop`` excluding the interiors of nested loops
    (those are judged by their own loop's check)."""
    stack = list(ast.iter_child_nodes(loop))
    while stack:
        n = stack.pop()
        yield n
        if not isinstance(n, (ast.For, ast.While)):
            stack.extend(ast.iter_child_nodes(n))


def _direct_add_folds(value: ast.AST, x: str) -> bool:
    """True when ``value`` contains ``... x + e ...`` with ``x`` as a
    *direct* operand of the + (catches ``x = x + e`` and
    ``x = e if c else x + e``; skips host-int shapes like
    ``n = a.shape[0] + (1 if n % 2 else 0)``)."""
    for n in ast.walk(value):
        if isinstance(n, ast.BinOp) and isinstance(n.op, ast.Add):
            for side, other in ((n.left, n.right), (n.right, n.left)):
                if isinstance(side, ast.Name) and side.id == x:
                    return other
    return None


class UnbarrieredFoldLoop(LintRule):
    rule = "DET002"
    title = "float fold loop without optimization_barrier"

    def check(self, mod: SourceModule):
        for loop in ast.walk(mod.tree):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            if _contains_barrier(loop):
                continue
            # gate: the enclosing function (or module) must touch
            # jnp/jax/lax at all — loops in pure host code (param
            # counting, text parsing) never fold traced arrays
            scope = loop
            while scope in mod.parents and not isinstance(
                    scope, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scope = mod.parents[scope]
            if not _mentions_jax(scope):
                continue
            folded = self._folded_names(loop)
            if folded:
                yield loop, (
                    f"{', '.join(f'`{n}`' for n in sorted(folded))} fold(s) "
                    f"accumulatively in a Python loop with no "
                    f"jax.lax.optimization_barrier — XLA may reassociate "
                    f"consecutive float adds across unrolled iterations "
                    f"(the PR 8 fusion bug)")

    def _folded_names(self, loop: ast.AST) -> Set[str]:
        # names bound inside the loop to plausibly-traced values: a fold
        # of such a name is a fold of array data, not of host ints
        jaxish: Set[str] = set()
        for stmt in ast.walk(loop):
            if isinstance(stmt, ast.Assign) and (
                    isinstance(stmt.value, ast.Call)
                    or _is_jaxish_expr(stmt.value, jaxish)):
                for t in stmt.targets:
                    jaxish |= _names_in(t)

        folded: Set[str] = set()
        for stmt in _direct_stmts(loop):
            # x = ... x + e ... (including `x = e if c else x + e`)
            if isinstance(stmt, ast.Assign) \
                    and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name):
                x = stmt.targets[0].id
                other = _direct_add_folds(stmt.value, x)
                if other is not None and _is_jaxish_expr(other, jaxish):
                    folded.add(x)
                    continue
            # x += e (traced e only)
            if isinstance(stmt, ast.AugAssign) \
                    and isinstance(stmt.op, ast.Add) \
                    and isinstance(stmt.target, ast.Name) \
                    and _is_jaxish_expr(stmt.value, jaxish):
                folded.add(stmt.target.id)
            # x, err = two_sum(x, e) / carry = policy.update(carry, c)
            elif isinstance(stmt, ast.Assign) \
                    and isinstance(stmt.value, ast.Call):
                callee = dotted_name(stmt.value.func) or ""
                if not _FOLD_CALLS.search(callee):
                    continue
                tgt_names: Set[str] = set()
                for t in stmt.targets:
                    tgt_names |= _names_in(t)
                arg_names: Set[str] = set()
                for a in stmt.value.args:
                    arg_names |= _names_in(a)
                folded |= tgt_names & arg_names
        return folded


# ---------------------------------------------------------------------------
# DET003 — scatter writes without explicit mode=
# ---------------------------------------------------------------------------

_SCATTER_METHODS = {"set", "add", "subtract", "multiply", "mul", "divide",
                    "div", "power", "min", "max", "apply", "get"}


class ModelessScatter(LintRule):
    rule = "DET003"
    title = ".at[...] write without explicit mode="

    def check(self, mod: SourceModule):
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not (isinstance(f, ast.Attribute)
                    and f.attr in _SCATTER_METHODS
                    and isinstance(f.value, ast.Subscript)
                    and isinstance(f.value.value, ast.Attribute)
                    and f.value.value.attr == "at"):
                continue
            if any(kw.arg == "mode" for kw in node.keywords):
                continue
            yield node, (f"`.at[...].{f.attr}()` without explicit mode= — "
                         f"the default silently drops OOB indices and "
                         f"*wraps negative ones*; state the intended "
                         f"behavior (mode=\"drop\" is bitwise-identical "
                         f"for in-range indices)")


# ---------------------------------------------------------------------------
# DET004 — order-dependent PRNG derivation in serving code
# ---------------------------------------------------------------------------


class SplitInServe(LintRule):
    rule = "DET004"
    title = "jax.random.split in per-request code"

    def applies(self, mod: SourceModule) -> bool:
        return _in_dirs(mod, {"serve"})

    def check(self, mod: SourceModule):
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            d = dotted_name(node.func) or ""
            parts = d.split(".")
            if parts[-1] == "split" and \
                    any("random" in p or p in ("jr", "jrandom")
                        for p in parts[:-1]):
                yield node, ("`random.split` chains depend on request "
                             "arrival order — derive per-request keys "
                             "with fold_in(seed, rid, step) "
                             "(docs/serving.md PRNG contract)")


# ---------------------------------------------------------------------------
# DET006 — f32 count/index arithmetic (exact only to 2^24)
# ---------------------------------------------------------------------------

_FLOAT_DTYPES = {"jnp.float32", "jnp.float64", "jnp.bfloat16",
                 "jax.numpy.float32", "np.float32"}


def _is_float_dtype_expr(node: Optional[ast.AST]) -> bool:
    if node is None:
        return False
    d = dotted_name(node)
    if d in _FLOAT_DTYPES:
        return True
    return isinstance(node, ast.Constant) and \
        isinstance(node.value, str) and "float" in node.value


def _is_float_ones(node: ast.AST) -> bool:
    """``jnp.ones(..., jnp.float32)`` / ``jnp.ones_like(x, jnp.float32)``
    — a count vector built in float."""
    if not isinstance(node, ast.Call):
        return False
    d = dotted_name(node.func) or ""
    if d.split(".")[-1] not in ("ones", "ones_like", "full", "full_like"):
        return False
    dtype_args = [kw.value for kw in node.keywords if kw.arg == "dtype"]
    dtype_args += node.args[1:]
    return any(_is_float_dtype_expr(a) for a in dtype_args)


class FloatCountArithmetic(LintRule):
    rule = "DET006"
    title = "f32 count/index arithmetic (exact only to 2^24)"

    def check(self, mod: SourceModule):
        # names bound (anywhere in the module) to float-ones vectors
        float_ones_names: Set[str] = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Assign) and _is_float_ones(node.value):
                for t in node.targets:
                    float_ones_names |= _names_in(t)

        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            callee = dotted_name(node.func) or ""
            tail = callee.split(".")[-1]
            # (a) float ones-vector fed to a *sum*/count accumulator
            if "sum" in tail or "count" in tail:
                for a in node.args:
                    if _is_float_ones(a) or (isinstance(a, ast.Name)
                                             and a.id in float_ones_names):
                        yield node, ("counting in f32: a float ones-vector "
                                     "accumulated by a sum saturates at "
                                     "2^24 (f32 integer grid); count in "
                                     "int32/int64 and cast after")
            # (b) psum of a float 1.0 — device counting in float
            if tail in ("psum", "pmean") and node.args:
                a0 = node.args[0]
                if (isinstance(a0, ast.Constant)
                        and isinstance(a0.value, float)) or \
                        (isinstance(a0, ast.Call)
                         and _is_float_dtype_expr(a0.func)):
                    yield node, ("device-counting via psum of a float "
                                 "constant — exact only to 2^24; psum an "
                                 "int and cast after")
            # (c) index grids materialized in float
            if tail in ("arange", "iota", "broadcasted_iota"):
                dtype_args = [kw.value for kw in node.keywords
                              if kw.arg == "dtype"]
                if tail == "arange":
                    dtype_args += node.args[3:]
                else:
                    dtype_args += node.args[:1]
                if any(_is_float_dtype_expr(a) for a in dtype_args):
                    yield node, ("index grid materialized in float — "
                                 "positions past 2^24 collide on the f32 "
                                 "integer grid; build indices in int and "
                                 "cast at the use site")


# ---------------------------------------------------------------------------
# DET005 — registry hook contract (reflection over the live registries)
# ---------------------------------------------------------------------------

_POLICY_HOOKS = {
    # hook -> (min positional args after self, required kwargs)
    "prepare_ctx": (2, ()),
    "domain_args": (1, ()),
    "map_rows": (1, ()),
    "to_domain": (2, ()),
    "prepare": (1, ()),
    "contrib": (2, ()),
    "contrib_lanes": (3, ("seg_offset", "lanes")),
    "init": (2, ()),
    "update": (2, ()),
    "merge": (2, ()),
    "merge_across": (2, ()),
    "carry_status": (1, ()),
    "finalize": (2, ()),
    "stage_costs": (1, ()),
    "domain_width": (1, ()),
}

_BACKEND_RUN_KWARGS = ("policy", "block_size", "interpret")


def _sig_accepts(fn, *, min_pos: int = 0,
                 kwargs: Sequence[str] = ()) -> Optional[str]:
    """None when ``fn``'s signature can take ``min_pos`` positional args
    and every kwarg in ``kwargs``; else a human-readable deficit."""
    import inspect
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return "signature not introspectable"
    params = list(sig.parameters.values())
    has_var_pos = any(p.kind is p.VAR_POSITIONAL for p in params)
    has_var_kw = any(p.kind is p.VAR_KEYWORD for p in params)
    n_pos = sum(p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
                for p in params)
    if n_pos < min_pos and not has_var_pos:
        return f"takes {n_pos} positional args, needs {min_pos}"
    names = {p.name for p in params}
    missing = [k for k in kwargs if k not in names and not has_var_kw]
    if missing:
        return f"missing keyword(s) {missing}"
    return None


def _def_site(obj):
    """(relpath, lineno) of a class/object definition, best-effort."""
    import inspect
    try:
        cls = obj if isinstance(obj, type) else type(obj)
        path = inspect.getsourcefile(cls)
        _, line = inspect.getsourcelines(cls)
        rel = str(walker.Path(path).resolve().relative_to(walker.repo_root()))
        return rel, line
    except (OSError, TypeError, ValueError):
        return "<registry>", 0


def check_registries() -> List[Finding]:
    """DET005: every registered policy/backend/op satisfies the hook
    contract its registry promises callers.  Runs against the *live*
    registries so a class registered from anywhere is held to the bar."""
    out: List[Finding] = []

    def finding(obj, msg, *, waived=False, reason=""):
        rel, line = _def_site(obj)
        out.append(Finding(rule="DET005", path=rel, line=line, message=msg,
                           waived=waived, reason=reason))

    try:
        import jax.numpy as jnp
        from repro.reduce.policy import POLICIES
        from repro.reduce.backends import BACKENDS
        from repro.reduce.algebra import REDUCE_OPS
    except Exception as e:    # loud, unwaivable: the checker itself broke
        out.append(Finding(rule="DET005", path="<registry>", line=0,
                           message=f"registry reflection failed to load: "
                                   f"{type(e).__name__}: {e}"))
        return out

    for name, p in sorted(POLICIES.items()):
        if getattr(p, "name", None) != name:
            finding(p, f"policy registered as {name!r} but .name is "
                       f"{getattr(p, 'name', None)!r}")
        for hook, (min_pos, kwargs) in _POLICY_HOOKS.items():
            fn = getattr(p, hook, None)
            if not callable(fn):
                finding(p, f"policy {name!r} missing required hook "
                           f"`{hook}`")
                continue
            deficit = _sig_accepts(fn, min_pos=min_pos, kwargs=kwargs)
            if deficit:
                finding(p, f"policy {name!r} hook `{hook}`: {deficit}")
        dts = getattr(p, "carry_dtypes", None)
        clen = getattr(p, "carry_len", None)
        if dts is None or clen is None or len(tuple(dts)) != clen:
            finding(p, f"policy {name!r}: len(carry_dtypes)="
                       f"{None if dts is None else len(tuple(dts))} != "
                       f"carry_len={clen}")
        elif getattr(p, "merge_is_add", False) and \
                not all(jnp.issubdtype(jnp.dtype(d), jnp.integer)
                        for d in dts):
            tol = TOLERATED_FLOAT_MERGE.get(name)
            finding(p, f"policy {name!r}: merge_is_add with non-integer "
                       f"carry leaves {tuple(str(jnp.dtype(d)) for d in dts)}"
                       f" — psum of floats is order-sensitive",
                    waived=tol is not None, reason=tol or "")

    for name, b in sorted(BACKENDS.items()):
        if b.name != name:
            finding(b, f"backend registered as {name!r} but .name is "
                       f"{b.name!r}")
        kwargs = list(_BACKEND_RUN_KWARGS)
        if getattr(b, "staged", False):
            kwargs += ["program", "to_domain", "prep_state"]
        if getattr(b, "distributed", False):
            kwargs += ["mesh", "axis_names"]
        deficit = _sig_accepts(b.run, min_pos=3, kwargs=kwargs)
        if deficit:
            finding(b.run, f"backend {name!r} run(): {deficit}")

    for name, op in sorted(REDUCE_OPS.items()):
        if getattr(op, "name", None) != name:
            finding(op, f"op registered as {name!r} but .name is "
                       f"{getattr(op, 'name', None)!r}")
        for hook, spec in (("pre", (1, ("weights", "coeffs"))),
                           ("post", (2, ()))):
            fn = getattr(op, hook, None)
            if not callable(fn):
                finding(op, f"op {name!r} missing required hook `{hook}`")
                continue
            deficit = _sig_accepts(fn, min_pos=spec[0], kwargs=spec[1])
            if deficit:
                finding(op, f"op {name!r} hook `{hook}`: {deficit}")
        comps = getattr(op, "components", None)
        if not isinstance(comps, int) or comps < 1:
            finding(op, f"op {name!r}: components must be a positive int, "
                       f"got {comps!r}")
        for req, takes in (("requires_weights", "takes_weights"),
                           ("requires_coeffs", "takes_coeffs")):
            if getattr(op, req, False) and not getattr(op, takes, False):
                finding(op, f"op {name!r}: {req} without {takes}")

    # apply source-level pragmas to reflection findings too
    cache = {}
    for f in out:
        if f.waived or f.path == "<registry>":
            continue
        p = walker.repo_root() / f.path
        if p not in cache and p.exists():
            cache[p] = walker.parse_module(p)
        mod = cache.get(p)
        if mod is None:
            continue
        node = ast.Module(body=[], type_ignores=[])
        node.lineno = f.line
        node.end_lineno = f.line
        w = mod.waiver_for("DET005", node)
        if w is not None:
            f.waived, f.reason = True, w.reason
    return out


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------

AST_RULES: List[LintRule] = [
    RawReduction(),
    UnbarrieredFoldLoop(),
    ModelessScatter(),
    SplitInServe(),
    FloatCountArithmetic(),
]

ALL_RULE_IDS = tuple(sorted({r.rule for r in AST_RULES} | {"DET005"}))


def run_lint(files: Sequence, *, rules: Optional[Set[str]] = None,
             registry: bool = True) -> List[Finding]:
    """Lint ``files`` (paths) with every AST rule, plus the registry
    reflection rule (DET005) unless ``registry=False``.  ``rules``
    filters to a subset of rule ids."""
    findings: List[Finding] = []
    for path in files:
        mod = walker.parse_module(path)
        for rule in AST_RULES:
            if rules and rule.rule not in rules:
                continue
            findings.extend(rule.run(mod))
    if registry and (not rules or "DET005" in rules):
        findings.extend(check_registries())
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings
