"""span-discipline: spans are context managers with pinned names.

The tracer's accounting depends on two conventions PR 6 established and
nothing enforced:

* a span is opened ONLY as a ``with`` context manager — a bare
  ``span(...)`` call never closes, so its duration never lands in the
  buffer, the stats sink never accumulates, and the stage histogram
  silently under-counts (the exact bug class the span/stats
  reconciliation test can only catch for instrumented paths);
* span names (and explicit ``lane=`` tags) come from the pinned schema
  (``obs.trace.SPAN_NAMES`` / ``obs.trace.LANES``) — an off-schema
  name falls out of every rollup, tracecat table, and histogram.

Checked: calls to ``span``/``_span`` (the engines' import alias) and
``<x>.span(...)`` on a tracer; non-literal names are skipped.
``obs/trace.py`` and ``obs/__init__.py`` — the definition sites whose
helpers *return* spans — are exempt.
"""

from __future__ import annotations

import ast
from typing import Iterator, Set

from dsi_tpu.analysis.core import (
    Finding,
    Project,
    Rule,
    SourceFile,
    dotted,
)
from dsi_tpu.obs.trace import LANES, SPAN_NAMES

_EXEMPT = ("dsi_tpu/obs/trace.py", "dsi_tpu/obs/__init__.py")


def _is_span_call(node: ast.Call) -> bool:
    name = dotted(node.func)
    return name in ("span", "_span") or name.endswith(".span")


class SpanDisciplineRule(Rule):
    rule_id = "span-discipline"
    summary = "span not context-managed, or off-schema span/lane name"

    def applies(self, rel: str) -> bool:
        return not rel.endswith(_EXEMPT)

    def check(self, module: SourceFile,
              project: Project) -> Iterator[Finding]:
        with_exprs: Set[int] = set()
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    with_exprs.add(id(item.context_expr))
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            if not _is_span_call(node):
                continue
            if id(node) not in with_exprs:
                yield Finding(
                    module.rel, node.lineno, node.col_offset,
                    self.rule_id,
                    "span opened outside a `with` statement — it never "
                    "closes, so its duration is lost to the trace, the "
                    "stats sink, and the stage histograms")
            if node.args and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                sname = node.args[0].value
                if sname not in SPAN_NAMES:
                    yield Finding(
                        module.rel, node.lineno, node.col_offset,
                        self.rule_id,
                        f"span name {sname!r} is not in the pinned "
                        f"schema (obs.trace.SPAN_NAMES) — add it there "
                        f"(a schema change) or use a pinned stage name")
            for kw in node.keywords:
                if kw.arg == "lane" and isinstance(kw.value,
                                                   ast.Constant) \
                        and isinstance(kw.value.value, str) \
                        and kw.value.value not in LANES:
                    yield Finding(
                        module.rel, node.lineno, node.col_offset,
                        self.rule_id,
                        f"lane {kw.value.value!r} is not in the pinned "
                        f"lane taxonomy (obs.trace.LANES)")
