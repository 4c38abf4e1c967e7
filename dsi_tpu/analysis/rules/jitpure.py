"""jit-purity: no wall-clock / randomness / environment inside jit.

A jit-compiled body executes at TRACE time on abstract values and is
then replayed from the compiled executable forever after — a
``time.time()``, ``random.random()``, ``np.random...`` draw, or
``os.environ`` read inside one is evaluated ONCE at compile and baked
into the program as a constant.  With the persistent compile cache the
constant then survives across processes and machines, which turns
"nondeterminism" into the worse failure: *stale* determinism that
changes whenever the cache misses.  (Host-side numpy RNG inside a jit
body is also a parity trap: the mesh A/B harness diffing two runs
bit-for-bit assumes the program text is the only input.)

The rule finds functions that are jit targets — decorated ``@jax.jit``
/ ``@partial(jax.jit, ...)``, or referenced by name in ``jax.jit(f)``
/ ``cached_compile("...", f, ...)``
calls (optionally wrapped in ``x64_scoped``) — and flags calls/reads
of: ``time.*``, ``random.*``, ``np.random.*``/``numpy.random.*``,
``os.environ``/``os.getenv``, ``datetime.now``/``utcnow``,
``uuid.*``, and ``open``/``input``.  Helper calls are not chased
(one level, documented); a deliberate exception is annotated
``# dsicheck: allow[jit-purity] <why>``.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Set

from dsi_tpu.analysis.core import (
    Finding,
    Project,
    Rule,
    SourceFile,
    dotted,
)

_JIT_CALLS = ("jax.jit", "jit")
_COMPILE_CALLS = ("cached_compile", "aotcache.cached_compile")
_WRAPPERS = ("x64_scoped", "jaxcompat.x64_scoped")

_BANNED_PREFIXES = (
    "time.", "random.", "np.random.", "numpy.random.", "uuid.",
    "secrets.",
)
_BANNED_EXACT = ("os.getenv", "os.urandom", "datetime.now",
                 "datetime.utcnow", "datetime.datetime.now",
                 "datetime.datetime.utcnow", "open", "input")
_BANNED_ATTRS = ("os.environ",)


def _jit_target_names(tree: ast.Module) -> Set[str]:
    """Names of functions handed to jit/cached_compile in this module."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = dotted(node.func)
        if name in _WRAPPERS and node.args:
            inner = node.args[0]
            if isinstance(inner, ast.Call):
                node = inner
                name = dotted(node.func)
        if name in _JIT_CALLS or name.endswith(
                tuple("." + j for j in _JIT_CALLS)):
            if node.args and isinstance(node.args[0], ast.Name):
                out.add(node.args[0].id)
        elif name in _COMPILE_CALLS or name.endswith(
                tuple("." + c for c in _COMPILE_CALLS)):
            # cached_compile(name, fn, ...)
            if len(node.args) >= 2 and isinstance(node.args[1],
                                                  ast.Name):
                out.add(node.args[1].id)
    return out


def _is_jit_decorated(fn: ast.FunctionDef) -> bool:
    for dec in fn.decorator_list:
        name = dotted(dec)
        if name in _JIT_CALLS or name.endswith((".jit",)):
            return True
        if isinstance(dec, ast.Call):
            cn = dotted(dec.func)
            if cn in _JIT_CALLS or cn.endswith((".jit",)):
                return True
            if cn in ("partial", "functools.partial") and dec.args:
                inner = dotted(dec.args[0])
                if inner in _JIT_CALLS or inner.endswith((".jit",)):
                    return True
    return False


class JitPurityRule(Rule):
    rule_id = "jit-purity"
    summary = "time/random/env read inside a jit-compiled body"

    def check(self, module: SourceFile,
              project: Project) -> Iterator[Finding]:
        targets = _jit_target_names(module.tree)
        fns: Dict[str, List[ast.FunctionDef]] = {}
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fns.setdefault(node.name, []).append(node)
        checked: Set[int] = set()
        for fn_list in fns.values():
            for fn in fn_list:
                if id(fn) in checked:
                    continue
                if fn.name in targets or _is_jit_decorated(fn):
                    checked.add(id(fn))
                    yield from self._check_body(module, fn)

    def _check_body(self, module: SourceFile,
                    fn: ast.FunctionDef) -> Iterator[Finding]:
        for node in ast.walk(fn):
            bad = None
            if isinstance(node, ast.Call):
                name = dotted(node.func)
                if name in _BANNED_EXACT or \
                        name.startswith(_BANNED_PREFIXES):
                    bad = f"{name}()"
            elif isinstance(node, (ast.Attribute, ast.Subscript)):
                name = dotted(node if isinstance(node, ast.Attribute)
                              else node.value)
                if name in _BANNED_ATTRS:
                    bad = name
            if bad is not None:
                yield Finding(
                    module.rel, node.lineno, node.col_offset,
                    self.rule_id,
                    f"{bad} inside jit target `{fn.name}` — evaluated "
                    f"once at trace time and baked into the compiled "
                    f"(and AOT-persisted) program as a constant")
