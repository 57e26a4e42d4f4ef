"""The AST rule catalog (DESIGN.md §14).

Every rule that used to be a regex in ``scripts/check_dispatch.py`` is an
AST visitor here — operating on parsed structure, not text, so aliasing
(``import time as t``), ``from``-imports and formatting cannot slip past
the gate — plus rules a line regex could never express (unthreaded RNG
keys, bare ``except:`` handlers, mutable default arguments).

A rule is an object with

  * ``id``        — stable kebab-case identifier (``# lint: disable=<id>``),
  * ``severity``  — ``error`` findings fail the gate,
  * ``anchor``    — the DESIGN.md section documenting the invariant,
  * ``doc``       — one-line description (shown by ``--rules``),
  * ``visit(tree, path, lines) -> [Finding]``.

Scoping mirrors the old gate exactly: each rule carries the allowed /
banned path prefixes (repo-relative posix) the regexes used, so the AST
engine reproduces every violation class the grep-gate caught. Tests stay
exempt by construction — the engine never scans ``tests/``.
"""
from __future__ import annotations

import ast
import re
from typing import Protocol, runtime_checkable

from repro.analysis.findings import Finding, Severity

__all__ = ["Rule", "all_rules", "rule_by_id", "register",
           "LEGACY_TIME_RE", "CLOCK_FNS"]

# the exact regex the pre-AST gate used for the serving-layer clock ban —
# kept importable so the regression suite can prove what it missed
# (``import time as t; t.monotonic()`` and ``from time import monotonic``)
LEGACY_TIME_RE = re.compile(
    r"\btime\.(monotonic|sleep|time|perf_counter)\s*\(")

CLOCK_FNS = ("monotonic", "sleep", "time", "perf_counter")


@runtime_checkable
class Rule(Protocol):
    """The rule protocol the engine drives."""

    id: str
    severity: Severity
    anchor: str
    doc: str

    def applies(self, path: str) -> bool: ...

    def visit(self, tree: ast.AST, path: str,
              lines: list[str]) -> list[Finding]: ...


_RULES: list["BaseRule"] = []


def register(cls):
    _RULES.append(cls())
    return cls


def all_rules() -> tuple["BaseRule", ...]:
    return tuple(_RULES)


def rule_by_id(rule_id: str) -> "BaseRule":
    for rule in _RULES:
        if rule.id == rule_id:
            return rule
    raise KeyError(f"no lint rule {rule_id!r}; known: "
                   f"{[r.id for r in _RULES]}")


# ---------------------------------------------------------------------------
# shared AST helpers

def _dotted(node: ast.AST) -> str:
    """Dotted name of an expression (``a.b.c``), or '' when not a plain
    name chain."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _call_name(call: ast.Call) -> str:
    return _dotted(call.func)


def _calls(tree: ast.AST) -> list[ast.Call]:
    return [n for n in ast.walk(tree) if isinstance(n, ast.Call)]


class BaseRule:
    """Common scoping + finding construction. Subclasses set the class
    attributes and implement ``check``."""

    id: str = ""
    severity: Severity = Severity.ERROR
    anchor: str = "DESIGN.md §14"
    doc: str = ""
    fix: str = ""
    # path scoping (repo-relative posix). ``only_prefixes=None`` means the
    # rule runs on every scanned file; exemptions are checked either way.
    only_prefixes: tuple[str, ...] | None = None
    exempt_prefixes: tuple[str, ...] = ()
    exempt_files: tuple[str, ...] = ()

    def applies(self, path: str) -> bool:
        if path in self.exempt_files or path.startswith(self.exempt_prefixes):
            return False
        if self.only_prefixes is None:
            return True
        return path.startswith(self.only_prefixes)

    def finding(self, path: str, line: int, message: str,
                lines: list[str], fix: str | None = None) -> Finding:
        snippet = lines[line - 1].strip() if 0 < line <= len(lines) else ""
        return Finding(path=path, line=line, rule=self.id,
                       severity=self.severity, message=message,
                       fix=self.fix if fix is None else fix,
                       snippet=snippet)

    def visit(self, tree: ast.AST, path: str,
              lines: list[str]) -> list[Finding]:
        return self.check(tree, path, lines)

    def check(self, tree: ast.AST, path: str,
              lines: list[str]) -> list[Finding]:  # pragma: no cover
        raise NotImplementedError


# ---------------------------------------------------------------------------
# AST ports of the grep-gates (scoping identical to scripts/check_dispatch)

_OPS_EXEMPT = ("src/repro/ops/", "src/repro/kernels/")
_OPS_EXEMPT_FILES = ("src/repro/core/conv.py",)


@register
class StringDispatchRule(BaseRule):
    """``path="ref"|"im2col"|"kernel"`` string dispatch outside the op
    registry (DESIGN.md §7)."""

    id = "string-dispatch"
    doc = ("path= string dispatch outside repro.ops — the registry is the "
           "single dispatch surface")
    anchor = "DESIGN.md §7"
    fix = "route the execution choice through repro.ops ExecPolicy(backend=)"
    exempt_prefixes = _OPS_EXEMPT
    exempt_files = _OPS_EXEMPT_FILES

    def check(self, tree, path, lines):
        out = []
        for call in _calls(tree):
            for kw in call.keywords:
                if kw.arg == "path" and isinstance(kw.value, ast.Constant) \
                        and kw.value.value in ("ref", "im2col", "kernel"):
                    out.append(self.finding(
                        path, kw.value.lineno,
                        f"string dispatch path={kw.value.value!r} outside "
                        f"the op registry", lines))
        return out


@register
class InterpretLiteralRule(BaseRule):
    """Hardcoded ``interpret=True/False`` outside the registry/kernels
    (DESIGN.md §7)."""

    id = "interpret-literal"
    doc = ("hardcoded interpret= literal outside repro.ops/kernels — "
           "interpret mode is an ExecPolicy decision")
    anchor = "DESIGN.md §7"
    fix = "let the registry auto-detect, or set ExecPolicy.interpret"
    exempt_prefixes = _OPS_EXEMPT
    exempt_files = _OPS_EXEMPT_FILES

    def check(self, tree, path, lines):
        out = []
        for call in _calls(tree):
            for kw in call.keywords:
                if kw.arg == "interpret" \
                        and isinstance(kw.value, ast.Constant) \
                        and kw.value.value in (True, False):
                    out.append(self.finding(
                        path, kw.value.lineno,
                        f"hardcoded interpret={kw.value.value} literal",
                        lines))
        return out


@register
class ConvChainRule(BaseRule):
    """Hand-rolled conv→relu→pool chain outside the graph compiler
    (DESIGN.md §8): the unfused pipeline ``fused_conv_block`` replaces."""

    id = "conv-chain"
    doc = ("hand-rolled conv2d_apply -> relu -> pool chain outside "
           "graph/models/kernels")
    anchor = "DESIGN.md §8"
    fix = ("compile the model (PaperCNN.compile / repro.graph) or call "
           "fused_conv_block")
    exempt_prefixes = ("src/repro/graph/", "src/repro/models/",
                       "src/repro/kernels/")
    WINDOW = 4                      # lines after the conv call to scan

    def check(self, tree, path, lines):
        conv, relu, pool = [], set(), set()
        for call in _calls(tree):
            name = _call_name(call).rsplit(".", 1)[-1]
            if name == "conv2d_apply":
                conv.append(call.lineno)
            elif name == "relu":
                relu.add(call.lineno)
            elif name in ("maxpool2", "reduce_window"):
                pool.add(call.lineno)
        out = []
        for ln in conv:
            window = range(ln, ln + 1 + self.WINDOW)
            if any(r in window for r in relu) and \
                    any(p in window for p in pool):
                out.append(self.finding(
                    path, ln, "hand-rolled conv->relu->pool chain", lines))
        return out


@register
class ShardMapConvRule(BaseRule):
    """``shard_map`` over a conv dispatch outside ``core/parallelism``
    (DESIGN.md §9): channel-parallel convs go through the placement
    pass, not ad-hoc collectives."""

    id = "shard-map-conv"
    doc = "hand-rolled shard_map over a conv outside core.parallelism/graph"
    anchor = "DESIGN.md §9"
    fix = ("compile with mesh= so the placement pass routes the stage "
           "through core.parallelism")
    exempt_prefixes = ("src/repro/graph/",)
    exempt_files = ("src/repro/core/parallelism.py",)
    WINDOW = 15                     # lines around shard_map( to scan
    _CONV = re.compile(r"\A(conv2d\w*|fused_conv\w*|_conv)\Z")

    def check(self, tree, path, lines):
        shard, conv = [], set()
        for call in _calls(tree):
            name = _call_name(call).rsplit(".", 1)[-1]
            if name == "shard_map":
                shard.append(call.lineno)
            elif self._CONV.match(name):
                conv.add(call.lineno)
        for node in ast.walk(tree):
            if isinstance(node, ast.Constant) \
                    and node.value in ("conv2d", "fused_conv_block"):
                conv.add(node.lineno)
        out = []
        for ln in shard:
            lo, hi = ln - self.WINDOW, ln + self.WINDOW
            if any(lo <= c <= hi for c in conv):
                out.append(self.finding(
                    path, ln, "hand-rolled shard_map over a conv", lines))
        return out


@register
class RawClockRule(BaseRule):
    """Raw ``time`` module use in the serving layer (DESIGN.md §11): all
    serving-layer timing goes through the injectable Clock seam so the
    whole stack runs under virtual time in tests.

    Unlike the old regex (``LEGACY_TIME_RE``), this rule tracks imports:
    ``import time as t`` + ``t.monotonic()`` and
    ``from time import monotonic`` are both findings."""

    id = "raw-clock"
    doc = ("raw time.* (incl. aliased/from-imports) in serve/ outside the "
           "Clock seam")
    anchor = "DESIGN.md §11"
    fix = "inject repro.serve.clock.Clock (VirtualClock in tests)"
    only_prefixes = ("src/repro/serve/",)
    exempt_files = ("src/repro/serve/clock.py",)

    def check(self, tree, path, lines):
        out = []
        aliases = {"time"}          # names that resolve to the time module
        from_names: set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name == "time":
                        aliases.add(alias.asname or alias.name)
                        out.append(self.finding(
                            path, node.lineno,
                            f"import of the time module"
                            + (f" (aliased as "
                               f"{alias.asname!r})" if alias.asname else ""),
                            lines))
            elif isinstance(node, ast.ImportFrom) and node.module == "time":
                for alias in node.names:
                    if alias.name in CLOCK_FNS or alias.name == "*":
                        from_names.add(alias.asname or alias.name)
                        out.append(self.finding(
                            path, node.lineno,
                            f"from-import of time.{alias.name}", lines))
        for call in _calls(tree):
            func = call.func
            if isinstance(func, ast.Attribute) \
                    and isinstance(func.value, ast.Name) \
                    and func.value.id in aliases \
                    and func.attr in CLOCK_FNS:
                out.append(self.finding(
                    path, call.lineno,
                    f"raw {func.value.id}.{func.attr}() in the serving "
                    f"layer", lines))
            elif isinstance(func, ast.Name) and func.id in from_names:
                out.append(self.finding(
                    path, call.lineno,
                    f"raw {func.id}() (from-imported clock) in the "
                    f"serving layer", lines))
        return out


# ---------------------------------------------------------------------------
# rules the regexes could not express

@register
class GlobalRandomRule(BaseRule):
    """Unthreaded randomness in library code: the module-global numpy RNG
    (hidden state, irreproducible across processes) and jax samplers fed
    an inline ``PRNGKey`` at the call site (key creation belongs to the
    caller, threaded down explicitly)."""

    id = "global-random"
    doc = ("np.random global-RNG call, or jax.random sampler with an "
           "inline PRNGKey, in src/repro")
    anchor = "DESIGN.md §14"
    fix = ("use np.random.RandomState(seed)/default_rng(seed), or thread "
           "an explicit jax key down from the caller")
    only_prefixes = ("src/repro/",)
    _NP_OK = ("RandomState", "default_rng", "Generator", "SeedSequence")
    _JAX_NONSAMPLERS = ("PRNGKey", "key", "split", "fold_in",
                        "wrap_key_data", "key_data", "clone")

    def check(self, tree, path, lines):
        out = []
        for call in _calls(tree):
            name = _call_name(call)
            if name.startswith(("np.random.", "numpy.random.")):
                fn = name.rsplit(".", 1)[-1]
                if fn not in self._NP_OK:
                    out.append(self.finding(
                        path, call.lineno,
                        f"module-global numpy RNG call {name}()", lines))
            elif name.startswith("jax.random.") or \
                    name.startswith("jrandom."):
                fn = name.rsplit(".", 1)[-1]
                if fn in self._JAX_NONSAMPLERS or not call.args:
                    continue
                key = call.args[0]
                if isinstance(key, ast.Call) and \
                        _call_name(key).rsplit(".", 1)[-1] in ("PRNGKey",
                                                               "key"):
                    out.append(self.finding(
                        path, call.lineno,
                        f"jax sampler {name}() creates its key inline "
                        f"instead of threading one", lines))
        return out


@register
class BareExceptRule(BaseRule):
    """Bare ``except:`` in library code — the serve/artifact fallback
    ladders must name what they catch, or they swallow
    KeyboardInterrupt/SystemExit and real bugs alike."""

    id = "bare-except"
    doc = "bare except: handler in src/repro"
    anchor = "DESIGN.md §12"
    fix = "name the exception types the fallback ladder handles"
    only_prefixes = ("src/repro/",)

    def check(self, tree, path, lines):
        return [self.finding(path, node.lineno,
                             "bare except: swallows everything incl. "
                             "KeyboardInterrupt", lines)
                for node in ast.walk(tree)
                if isinstance(node, ast.ExceptHandler) and node.type is None]


@register
class MutableDefaultRule(BaseRule):
    """Mutable default arguments in config code — a shared mutable
    default aliases across every config instance."""

    id = "mutable-default"
    doc = "mutable default argument in src/repro/configs"
    anchor = "DESIGN.md §14"
    fix = "default to None (or a tuple/frozen value) and build inside"
    only_prefixes = ("src/repro/configs/",)
    _MUTABLE_CALLS = ("list", "dict", "set", "defaultdict", "OrderedDict")

    def _is_mutable(self, node) -> bool:
        if isinstance(node, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                             ast.DictComp, ast.SetComp)):
            return True
        return isinstance(node, ast.Call) and \
            _call_name(node).rsplit(".", 1)[-1] in self._MUTABLE_CALLS

    def check(self, tree, path, lines):
        out = []
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.Lambda)):
                continue
            args = node.args
            for default in (*args.defaults, *args.kw_defaults):
                if default is not None and self._is_mutable(default):
                    name = getattr(node, "name", "<lambda>")
                    out.append(self.finding(
                        path, default.lineno,
                        f"mutable default argument on {name}()", lines))
        return out
