"""AST lint: this repo's hard-won jax sharp edges as named rules.

Each rule encodes a bug class a past PR actually hit, with file:line
diagnostics:

- ``DHM001`` eager ``jnp.concatenate``/``jnp.stack`` on host paths in
  serving code — varying request shapes retrace the op per shape
  (~100 ms/flush); pack with numpy on the host instead.
- ``DHM002`` param stacking (``jnp.stack``/``jnp.concatenate``) inside a
  jitted function — the params are re-stacked on every dispatch instead
  of staying resident; box and stack eagerly, pass resident leaves as
  arguments.
- ``DHM003`` timing a jax dispatch without ``block_until_ready`` —
  async dispatch returns before the work runs, so the window measures
  nothing.
- ``DHM004`` bare ``except:`` or a swallowed ``RequestError`` in the
  degradation ladder — failures must demote or surface, never vanish.
- ``DHM005`` float64 on the device path — jax silently truncates to
  f32 without x64 enabled, so the cast is at best a no-op and at worst
  a 2x memory surprise when x64 is on.
- ``DHM006`` a background thread created in serving code with no
  timeout-bounded ``join`` anywhere in the module — a wedged dispatch
  leaks the thread past interpreter shutdown (the PR-9 ``stop()`` bug
  class); shutdown paths must join with a timeout and fail loudly.

Rules are scoped by path pattern (``fnmatch``; ``*`` crosses
directories) so e.g. the serving-path rules never fire on kernel
bodies. The module is accelerator-free: pure ``ast``, no jax import.
"""

from __future__ import annotations

import ast
import dataclasses
import fnmatch
import os
from typing import Callable, Dict, List, Tuple

from repro.analysis.findings import Finding

# Names under which the degradation ladder's structured request errors
# travel (engine.py) — swallowing one hides a serving failure (DHM004).
_REQUEST_ERRORS = {
    "RequestError", "DeadlineExceeded", "Rejected", "Shed",
    "InvalidRequest", "BatchFailed", "CircuitOpen",
}

_TIME_CALLS = {
    "time.time", "time.perf_counter", "time.monotonic",
    "perf_counter", "monotonic",
}


@dataclasses.dataclass(frozen=True)
class Rule:
    id: str
    name: str
    doc: str
    path_globs: tuple  # fnmatch patterns against the posix relpath
    fn: Callable  # (ast.Module, src: str, relpath: str) -> [(line, msg)]

    def applies_to(self, relpath: str) -> bool:
        p = relpath.replace(os.sep, "/")
        return any(fnmatch.fnmatch(p, g) for g in self.path_globs)


RULES: Dict[str, Rule] = {}


def rule(id: str, *, name: str, path_globs):
    def deco(fn):
        if id in RULES:
            raise ValueError(f"duplicate rule id {id!r}")
        RULES[id] = Rule(
            id=id, name=name, doc=fn.__doc__ or "",
            path_globs=tuple(path_globs), fn=fn,
        )
        return fn

    return deco


# ---------------------------------------------------------------------------
# shared AST helpers


def _dotted(node) -> str:
    """Best-effort dotted source name of a call target ('jnp.stack',
    'time.perf_counter', 'jax.jit', ...); '' when not name-shaped."""
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return f"{base}.{node.attr}" if base else node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


def _parent_functions(tree) -> Dict[ast.AST, ast.AST]:
    """Map every node to its innermost enclosing function def (or None)."""
    owner: Dict[ast.AST, ast.AST] = {}

    def walk(node, fn):
        for child in ast.iter_child_nodes(node):
            owner[child] = fn
            walk(
                child,
                child
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef))
                else fn,
            )

    walk(tree, None)
    return owner


def _jitted_functions(tree) -> set:
    """Function defs that become jit traces: decorated with jax.jit (or
    partial(jax.jit, ...)), or later passed to a jax.jit(...) call by
    name anywhere in the module."""
    jitted = set()
    by_name: Dict[str, List[ast.AST]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            by_name.setdefault(node.name, []).append(node)
            for dec in node.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                nm = _dotted(target)
                if nm.endswith("jit"):
                    jitted.add(node)
                elif nm.endswith("partial") and isinstance(dec, ast.Call):
                    if any(_dotted(a).endswith("jit") for a in dec.args):
                        jitted.add(node)
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _dotted(node.func).endswith(
            ("jax.jit", "jax.pmap")
        ):
            for arg in node.args[:1]:
                for fndef in by_name.get(_dotted(arg), []):
                    jitted.add(fndef)
    return jitted


def _enclosing_chain(node, owner):
    fn = owner.get(node)
    while fn is not None:
        yield fn
        fn = owner.get(fn)


# ---------------------------------------------------------------------------
# rules


@rule(
    "DHM001",
    name="eager-concat-on-host-path",
    path_globs=("*core/dhm/engine.py", "*serve*.py"),
)
def _eager_concat(tree, src, relpath):
    """Eager jnp.concatenate/jnp.stack in serving code outside any jit:
    every distinct request-batch shape retraces the op (the PR-6
    100 ms/flush recompile). Pack with numpy on the host."""
    owner = _parent_functions(tree)
    jitted = _jitted_functions(tree)
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        nm = _dotted(node.func)
        if nm not in ("jnp.concatenate", "jnp.stack"):
            continue
        if any(fn in jitted for fn in _enclosing_chain(node, owner)):
            continue  # inside a jit trace: DHM002's domain
        out.append((
            node.lineno,
            f"eager {nm} on the serving host path retraces per shape — "
            "pack with np.concatenate/np.stack instead",
        ))
    return out


@rule(
    "DHM002",
    name="param-stack-inside-jit",
    path_globs=(
        "*core/dhm/pipeline.py", "*core/dhm/engine.py",
        "*core/dhm/compiler.py",
    ),
)
def _stack_inside_jit(tree, src, relpath):
    """jnp.stack/jnp.concatenate inside a jitted function: the params are
    re-stacked on every dispatch instead of staying resident. Box + stack
    eagerly; pass resident leaves as args."""
    owner = _parent_functions(tree)
    jitted = _jitted_functions(tree)
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        nm = _dotted(node.func)
        if nm not in ("jnp.concatenate", "jnp.stack"):
            continue
        if any(fn in jitted for fn in _enclosing_chain(node, owner)):
            out.append((
                node.lineno,
                f"{nm} inside a jitted function — stack params eagerly "
                "outside the trace and pass the resident leaves in",
            ))
    return out


@rule(
    "DHM003",
    name="timing-without-block",
    path_globs=("*bench*.py", "*benchmarks/*"),
)
def _timing_without_block(tree, src, relpath):
    """A timing window around a jax dispatch with no block_until_ready
    in the function: async dispatch returns immediately, so the window
    under-reports (the PR-3 bug class)."""
    owner = _parent_functions(tree)
    out = []
    fns = [
        n for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    ]
    for fn in fns:
        time_lines, dispatches, blocks = [], [], False
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            if owner.get(node) is not fn:
                continue  # a call inside a nested def belongs to that def
            nm = _dotted(node.func)
            if nm in _TIME_CALLS:
                time_lines.append(node.lineno)
            if "block_until_ready" in nm:
                blocks = True
            if (
                nm.startswith(("jnp.", "jax."))
                and "block_until_ready" not in nm
            ):
                dispatches.append(node)
        if blocks or len(time_lines) < 2:
            continue
        lo, hi = min(time_lines), max(time_lines)
        for node in dispatches:
            if lo < node.lineno < hi:
                out.append((
                    node.lineno,
                    f"jax dispatch {_dotted(node.func)} timed without "
                    "block_until_ready — async dispatch under-reports",
                ))
    return out


@rule(
    "DHM004",
    name="swallowed-request-error",
    path_globs=("*core/dhm/*.py",),
)
def _swallowed_errors(tree, src, relpath):
    """Bare ``except:`` or a RequestError-family handler whose body only
    passes: a degradation-ladder failure silently vanishes instead of
    demoting or surfacing."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if node.type is None:
            out.append((
                node.lineno,
                "bare except: swallows every failure including "
                "KeyboardInterrupt — name the exception",
            ))
            continue
        types = (
            node.type.elts if isinstance(node.type, ast.Tuple)
            else [node.type]
        )
        names = {_dotted(t).rsplit(".", 1)[-1] for t in types}
        if not (names & _REQUEST_ERRORS):
            continue
        body_is_noop = all(
            isinstance(stmt, (ast.Pass, ast.Continue))
            or (
                isinstance(stmt, ast.Expr)
                and isinstance(stmt.value, ast.Constant)
            )
            for stmt in node.body
        )
        if body_is_noop:
            out.append((
                node.lineno,
                f"swallowed {sorted(names & _REQUEST_ERRORS)} — a request "
                "failure must demote, complete the request, or re-raise",
            ))
    return out


@rule("DHM005", name="float64-on-device-path", path_globs=("*.py",))
def _float64(tree, src, relpath):
    """float64 on the device path: without x64 enabled jax silently
    truncates to f32 (the cast is a no-op); with it, a 2x memory
    surprise. Stay in float32."""
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr == "float64":
            base = _dotted(node.value)
            if base.endswith("jnp") or base.endswith("jax.numpy"):
                out.append((
                    node.lineno,
                    "jnp.float64 — jax runs f32 unless x64 is enabled; "
                    "this cast silently truncates",
                ))
        if isinstance(node, ast.Call):
            nm = _dotted(node.func)
            suspects = [
                a for a in node.args
                if nm.endswith(".astype") or nm.endswith(".asarray")
            ] + [kw.value for kw in node.keywords if kw.arg == "dtype"]
            for a in suspects:
                if isinstance(a, ast.Constant) and a.value == "float64":
                    out.append((
                        a.lineno,
                        '"float64" dtype on a device value — jax silently '
                        "truncates to f32 without x64",
                    ))
    return out


@rule(
    "DHM006",
    name="unbounded-background-thread",
    path_globs=(
        "*core/dhm/engine.py", "*core/dhm/multitenant.py", "*serve*.py",
    ),
)
def _unbounded_background_thread(tree, src, relpath):
    """A serving module that constructs ``threading.Thread`` must also
    contain a timeout-bounded ``.join(...)`` — an unbounded (or absent)
    join lets a dispatch wedged past the watchdog leak the thread into
    interpreter shutdown. Bound the join and fail loudly on expiry."""
    thread_ctors = []
    bounded_join = False
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        nm = _dotted(node.func)
        if nm in ("threading.Thread", "Thread"):
            thread_ctors.append(node)
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "join"
            # a str.join ('; '.join(...)) is not a thread join
            and not (
                isinstance(node.func.value, ast.Constant)
                and isinstance(node.func.value.value, str)
            )
            and (
                node.args
                or any(kw.arg == "timeout" for kw in node.keywords)
            )
        ):
            bounded_join = True
    if bounded_join:
        return []
    return [
        (
            node.lineno,
            "background thread created but the module has no "
            "timeout-bounded .join(...) — a wedged dispatch leaks the "
            "thread past shutdown; join with a timeout and fail loudly",
        )
        for node in thread_ctors
    ]


# ---------------------------------------------------------------------------
# driver


def lint_source(
    src: str, relpath: str, rules=None
) -> List[Finding]:
    """Lint one file's source; returns findings (never raises on a
    syntactically valid file)."""
    if rules is None:
        active = list(RULES.values())
    elif isinstance(rules, dict):
        active = list(rules.values())
    else:
        active = list(rules)
    tree = ast.parse(src)
    findings = []
    for r in active:
        if not r.applies_to(relpath):
            continue
        for line, msg in r.fn(tree, src, relpath):
            findings.append(Finding(
                rule=r.id, name=r.name, severity="error", message=msg,
                where=f"{relpath}:{line}",
            ))
    return findings


def lint_paths(paths, *, root: str = ".", rules=None) -> List[Finding]:
    """Walk ``paths`` (files or directories) and lint every ``.py`` file;
    ``where`` carries paths relative to ``root``."""
    findings: List[Finding] = []
    files: List[Tuple[str, str]] = []
    for p in paths:
        if os.path.isfile(p):
            files.append((p, os.path.relpath(p, root)))
        else:
            for dirpath, _dirnames, filenames in os.walk(p):
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        full = os.path.join(dirpath, fn)
                        files.append((full, os.path.relpath(full, root)))
    for full, rel in files:
        with open(full, encoding="utf-8") as fh:
            src = fh.read()
        try:
            findings.extend(lint_source(src, rel, rules=rules))
        except SyntaxError as e:
            findings.append(Finding(
                rule="DHM000", name="syntax-error", severity="error",
                message=f"file does not parse: {e}", where=f"{rel}:{e.lineno}",
            ))
    return findings
