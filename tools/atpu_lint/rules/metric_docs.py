"""Rule ``metric-docs``: the observability doc and the telemetry surface agree
in BOTH directions — for registry metrics AND for span/flight-event names.

Forward (ported from ``tools/check_metric_docs.py``): any literal metric name
passed to ``registry.counter(...)``, ``registry.gauge(...)`` or
``registry.histogram(...)`` inside ``accelerate_tpu/`` must appear verbatim
in ``docs/usage/observability.md`` — the doc is the operator-facing contract
for what a ``/metrics`` scrape can contain, and an undocumented gauge is
invisible to whoever has to build the dashboard.  The same holds for
namespaced span and flight-event names (``tracer.span("serve/...")``,
``recorder.record("serve/...")``, ``recorder.heartbeat("serve/...")``, and a
span's name handed on as a ``span="train/..."`` keyword): an
undocumented event kind is noise to whoever reads a ``/debug/flight`` ring
during an incident.

Reverse (new with the port — the old script was asymmetric): every concrete
metric name in the doc's metric table must still be emitted somewhere, or the
row is an *orphan* that sends the dashboard builder hunting for a series that
no longer exists.  A doc name counts as emitted when it matches a literal
registration OR a dynamic f-string family (``f"serve/{k}_total"`` matches
``serve/preemptions_total``).  Doc names carrying ``*`` are documented
globs and skipped; so are names outside the table's metrics column.
Span/flight-event names get the same orphan check against the doc's
"Span & flight-event index" section: its table rows (first cell) must each
match a ``span``/``record``/``heartbeat`` literal still in the tree.

Families (per-tenant / per-class / per-SLO names) close the loop in both
directions too.  A doc token written with ``<...>`` placeholders — e.g.
``serve/ttft_s_tenant_<tenant>`` — is a *family row*: its placeholder-
stripped instance (``serve/ttft_s_tenant_tenant``) must match some f-string
registration pattern (``f"serve/ttft_s_tenant_{tenant}"``), or the family
row is an orphan like any concrete row.  Conversely every f-string
registration must be documented — once, as a family row (or by a concrete
token the pattern covers); an undocumented ``f"serve/slo_burn_rate_{name}"``
is exactly as invisible to the dashboard builder as an undocumented literal.

Only string-literal (or f-string) first arguments are checked; names built
from opaque variables are skipped, as are un-namespaced span names (no
``/``, e.g. ``span("phase")`` in examples).  ``# noqa: metric-docs`` on the
emitting line exempts it.

The orphan direction runs only when the whole ``accelerate_tpu`` package is
on the lint surface: on a partial run (``python -m tools.atpu_lint
accelerate_tpu/serving``) the absence of a registration proves nothing.
"""

from __future__ import annotations

import ast
import re
from typing import List, Optional, Tuple

from ..core import Diagnostic, Rule

FACTORIES = ("counter", "gauge", "histogram")
EVENT_EMITTERS = ("span", "record", "heartbeat")
_CONCRETE = re.compile(r"[a-z0-9_]+(?:/[a-z0-9_]+)+")
_EVENT_SECTION = "span & flight-event index"


class MetricDocsRule(Rule):
    id = "metric-docs"
    summary = "every emitted metric is documented; every documented metric is emitted"

    def __init__(self):
        self._literals: List[Tuple[str, int, str, str]] = []  # rel, line, kind, name
        # rel, line, kind, compiled pattern, display form (``serve/<...>_total``)
        self._patterns: List[Tuple[str, int, str, re.Pattern, str]] = []
        self._event_literals: List[Tuple[str, int, str, str]] = []
        self._event_patterns: List[Tuple[str, int, str, re.Pattern, str]] = []

    def applies_to(self, rel: str) -> bool:
        return rel.startswith("accelerate_tpu/")

    def visit(self, tree, src, ctx) -> List[Diagnostic]:
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            for kw in node.keywords:
                # a span's name handed to whoever opens it
                # (``RecompileWatchdog(fn, span="train/dispatch")``)
                if (kw.arg == "span" and isinstance(kw.value, ast.Constant)
                        and isinstance(kw.value.value, str)
                        and _CONCRETE.fullmatch(kw.value.value)):
                    self._event_literals.append(
                        (ctx.rel, node.lineno, "span", kw.value.value)
                    )
            if not node.args:
                continue
            if isinstance(node.func, ast.Attribute):
                attr = node.func.attr
            elif isinstance(node.func, ast.Name):
                # the module-level ``span("...")`` helper from telemetry
                attr = node.func.id if node.func.id == "span" else None
            else:
                continue
            first = node.args[0]
            if attr in FACTORIES:
                if isinstance(first, ast.Constant) and isinstance(first.value, str):
                    self._literals.append((ctx.rel, node.lineno, attr, first.value))
                elif isinstance(first, ast.JoinedStr):
                    pattern, display = self._joined_pattern(first)
                    self._patterns.append(
                        (ctx.rel, node.lineno, attr, pattern, display)
                    )
            elif attr in EVENT_EMITTERS:
                if isinstance(first, ast.Constant) and isinstance(first.value, str):
                    # only namespaced names are part of the contract — bare
                    # span names ("phase", function qualnames) are ad hoc
                    if _CONCRETE.fullmatch(first.value):
                        self._event_literals.append(
                            (ctx.rel, node.lineno, attr, first.value)
                        )
                elif isinstance(first, ast.JoinedStr):
                    pattern, display = self._joined_pattern(first)
                    self._event_patterns.append(
                        (ctx.rel, node.lineno, attr, pattern, display)
                    )
        return []

    @staticmethod
    def _joined_pattern(node: ast.JoinedStr) -> Tuple[re.Pattern, str]:
        """Compile an f-string registration into ``(match pattern, display)``
        — the display form writes each interpolation as ``<...>``, the same
        placeholder convention family rows use in the doc."""
        parts = []
        display = []
        for piece in node.values:
            if isinstance(piece, ast.Constant):
                parts.append(re.escape(str(piece.value)))
                display.append(str(piece.value))
            else:
                parts.append(r".+")
                display.append("<...>")
        return re.compile("".join(parts)), "".join(display)

    @staticmethod
    def _family_instance(token: str) -> "Optional[str]":
        """A doc token with ``<...>`` placeholders (``serve/ttft_s_tenant_
        <tenant>``) collapses to a concrete *instance* (``serve/ttft_s_
        tenant_tenant``) that f-string registration patterns can fullmatch.
        Returns ``None`` for non-family tokens, globs, and malformed names.
        """
        if "<" not in token or "*" in token:
            return None
        instance = re.sub(r"<([a-z0-9_]+)>", r"\1", token)
        if "<" in instance or ">" in instance:
            return None
        return instance if _CONCRETE.fullmatch(instance) else None

    def finalize(self, project) -> List[Diagnostic]:
        doc_rel = project.observability_doc
        doc_path = project.root / doc_rel
        if not doc_path.exists():
            if not (self._literals or self._event_literals
                    or self._patterns or self._event_patterns):
                return []
            return [Diagnostic(doc_rel, 1, self.id, f"missing {doc_rel}")]
        doc_text = doc_path.read_text()
        out: List[Diagnostic] = []
        for rel, lineno, kind, name in self._literals:
            if name not in doc_text:
                out.append(Diagnostic(
                    rel, lineno, self.id,
                    f"{kind} '{name}' is not documented in {doc_rel}",
                ))
        for rel, lineno, kind, name in self._event_literals:
            if name not in doc_text:
                out.append(Diagnostic(
                    rel, lineno, self.id,
                    f"{kind} event '{name}' is not documented in {doc_rel}",
                ))
        # forward, family direction: an f-string registration is documented
        # when its pattern covers some backticked doc token — a concrete name
        # or a ``<...>`` family row's placeholder-stripped instance.  Tokens
        # are extracted per line: a whole-doc scan would mispair the
        # backticks of ``` code fences with inline ones and shift every
        # token after the first fence.
        doc_tokens = set()
        for doc_line in doc_text.splitlines():
            if doc_line.lstrip().startswith("```"):
                continue
            doc_tokens.update(re.findall(r"`([^`]+)`", doc_line))
        covered = {t for t in doc_tokens if _CONCRETE.fullmatch(t)}
        covered.update(
            inst for inst in map(self._family_instance, doc_tokens)
            if inst is not None
        )
        for rel, lineno, kind, pattern, display in self._patterns:
            if not any(pattern.fullmatch(t) for t in covered):
                out.append(Diagnostic(
                    rel, lineno, self.id,
                    f"{kind} family '{display}' is not documented in "
                    f"{doc_rel} (document it once as a family row, e.g. "
                    f"`{display.replace('<...>', '<label>')}`)",
                ))
        for rel, lineno, kind, pattern, display in self._event_patterns:
            if not any(pattern.fullmatch(t) for t in covered):
                out.append(Diagnostic(
                    rel, lineno, self.id,
                    f"{kind} event family '{display}' is not documented in "
                    f"{doc_rel}",
                ))
        if not self._covers_package(project):
            return out
        emitted = {name for _, _, _, name in self._literals}
        for lineno, name in self._doc_table_names(doc_text):
            instance = self._family_instance(name)
            if instance is not None:
                if instance in emitted or any(
                    p.fullmatch(instance) for _, _, _, p, _ in self._patterns
                ):
                    continue
                out.append(Diagnostic(
                    doc_rel, lineno, self.id,
                    f"orphan doc row: metric family '{name}' is documented "
                    "but no f-string registry.counter/gauge/histogram call "
                    "emits it",
                    src_line=name,
                ))
                continue
            if name in emitted or any(
                p.fullmatch(name) for _, _, _, p, _ in self._patterns
            ):
                continue
            out.append(Diagnostic(
                doc_rel, lineno, self.id,
                f"orphan doc row: metric '{name}' is documented but no longer "
                "emitted by any registry.counter/gauge/histogram call",
                src_line=name,
            ))
        event_names = {name for _, _, _, name in self._event_literals}
        for lineno, name in self._event_index_names(doc_text):
            instance = self._family_instance(name)
            if instance is not None:
                if instance in event_names or any(
                    p.fullmatch(instance) for _, _, _, p, _ in self._event_patterns
                ):
                    continue
                out.append(Diagnostic(
                    doc_rel, lineno, self.id,
                    f"orphan doc row: span/flight-event family '{name}' is "
                    "documented but no f-string span/record/heartbeat call "
                    "emits it",
                    src_line=name,
                ))
                continue
            if name in event_names or any(
                p.fullmatch(name) for _, _, _, p, _ in self._event_patterns
            ):
                continue
            out.append(Diagnostic(
                doc_rel, lineno, self.id,
                f"orphan doc row: span/flight-event '{name}' is documented "
                "but no longer emitted by any span/record/heartbeat call",
                src_line=name,
            ))
        return out

    @staticmethod
    def _covers_package(project) -> bool:
        """True when every lintable file of ``accelerate_tpu/`` was visited
        this run — the precondition for "nothing emits this name" to mean
        anything.  Fixture projects without the package count as covered."""
        pkg = project.root / "accelerate_tpu"
        if not pkg.is_dir():
            return True
        visited = {ctx.rel for ctx in project.files}
        for f in pkg.rglob("*.py"):
            rel = project.rel(f)
            if "__pycache__" in rel.split("/"):
                continue
            if rel not in visited:
                return False
        return True

    @staticmethod
    def _doc_table_names(doc_text: str) -> List[Tuple[int, str]]:
        """Metric names in the metrics column (cell 2) of markdown table
        rows: concrete names plus ``<...>`` family rows (orphan-checked
        against f-string registrations via :meth:`_family_instance`).
        Backticked tokens with ``*`` are documented globs and skipped.  Rows
        inside the span/event index section belong to
        :meth:`_event_index_names`, not here."""
        found = []
        in_event_section = False
        for i, line in enumerate(doc_text.splitlines(), start=1):
            if line.startswith("#"):
                in_event_section = _EVENT_SECTION in line.lower()
                continue
            if in_event_section or not line.lstrip().startswith("|"):
                continue
            cells = line.split("|")
            if len(cells) < 4:
                continue
            for m in re.finditer(r"`([^`]+)`", cells[2]):
                token = m.group(1)
                if "*" in token:
                    continue
                if "<" in token:
                    if MetricDocsRule._family_instance(token) is not None:
                        found.append((i, token))
                    continue
                if _CONCRETE.fullmatch(token):
                    found.append((i, token))
        return found

    @staticmethod
    def _event_index_names(doc_text: str) -> List[Tuple[int, str]]:
        """Span/flight-event names from the doc's "Span & flight-event
        index" section: the backticked tokens of each table row's first
        cell, until the next heading — concrete names plus ``<...>`` family
        rows; ``*`` globs are skipped."""
        found = []
        in_section = False
        for i, line in enumerate(doc_text.splitlines(), start=1):
            if line.startswith("#"):
                in_section = _EVENT_SECTION in line.lower()
                continue
            if not in_section or not line.lstrip().startswith("|"):
                continue
            cells = line.split("|")
            if len(cells) < 3:
                continue
            for m in re.finditer(r"`([^`]+)`", cells[1]):
                token = m.group(1)
                if "*" in token:
                    continue
                if "<" in token:
                    if MetricDocsRule._family_instance(token) is not None:
                        found.append((i, token))
                    continue
                if _CONCRETE.fullmatch(token):
                    found.append((i, token))
        return found
