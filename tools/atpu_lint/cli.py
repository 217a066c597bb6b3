"""atpu-lint command line: ``python -m tools.atpu_lint [paths...]``.

Exit codes: 0 clean, 1 findings, 2 usage/config error.  ``--format json``
emits a machine-readable report (consumed by ``make lint-json`` and CI
artifacts); warnings (legacy-pragma migration notices, skipped cross-tree
checks) go to stderr in both formats and never affect the exit code.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import List, Optional

from .baseline import (
    DEFAULT_BASELINE,
    baseline_notes_missing,
    load_baseline,
    write_baseline,
)
from .core import Project, Report, Runner
from .rules import ALL_RULES, get_rules

#: default lint surface — everything `make quality` covers
DEFAULT_PATHS = ["accelerate_tpu", "tests", "tools", "bench.py"]


def repo_root() -> Path:
    # tools/atpu_lint/cli.py -> repo root is two parents above the package
    return Path(__file__).resolve().parent.parent.parent


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="python -m tools.atpu_lint",
        description="unified AST/dataflow lint for the accelerate_tpu tree",
    )
    parser.add_argument(
        "paths", nargs="*", default=None,
        help=f"files/directories to lint (default: {' '.join(DEFAULT_PATHS)})",
    )
    parser.add_argument(
        "--format", choices=("text", "json"), default="text",
        help="report format (default: text)",
    )
    parser.add_argument(
        "--select", action="append", default=None, metavar="RULE-ID",
        help="run only these rule ids (repeatable or comma-separated)",
    )
    parser.add_argument(
        "--baseline", default=None, metavar="PATH",
        help=f"baseline file (default: {DEFAULT_BASELINE} when present)",
    )
    parser.add_argument(
        "--no-baseline", action="store_true",
        help="ignore the default baseline even if it exists",
    )
    parser.add_argument(
        "--write-baseline", action="store_true",
        help="write current findings to the baseline file and exit 0",
    )
    parser.add_argument(
        "--list-rules", action="store_true",
        help="print the registered rule ids and exit",
    )
    return parser


def _resolve_select(values: Optional[List[str]]) -> Optional[List[str]]:
    if values is None:
        return None
    out: List[str] = []
    for v in values:
        out.extend(tok.strip() for tok in v.split(",") if tok.strip())
    return out


def _render_text(report: Report, stream) -> None:
    for diag in report.diagnostics:
        stream.write(diag.render() + "\n")
    tail = f"{len(report.diagnostics)} finding(s) in {report.files_checked} file(s)"
    if report.suppressed:
        tail += f", {report.suppressed} noqa-suppressed"
    if report.baselined:
        tail += f", {len(report.baselined)} baselined"
    stream.write(tail + "\n")


def _render_json(report: Report, stream) -> None:
    payload = {
        "findings": [d.to_json() for d in report.diagnostics],
        "suppressed": report.suppressed,
        "baselined": [d.to_json() for d in report.baselined],
        "files_checked": report.files_checked,
        "warnings": report.warnings,
    }
    stream.write(json.dumps(payload, indent=2, sort_keys=True) + "\n")


def main(argv: Optional[List[str]] = None, root: Optional[Path] = None,
         stdout=None, stderr=None) -> int:
    stdout = stdout or sys.stdout
    stderr = stderr or sys.stderr
    args = build_parser().parse_args(argv)
    root = root or repo_root()

    if args.list_rules:
        for cls in ALL_RULES:
            stdout.write(f"{cls.id:24} {cls.summary}\n")
        return 0

    try:
        rules = get_rules(_resolve_select(args.select))
    except KeyError as exc:
        stderr.write(f"atpu-lint: {exc.args[0]}\n")
        return 2

    baseline_path = root / (args.baseline or DEFAULT_BASELINE)
    baseline = {}
    if not args.no_baseline and not args.write_baseline:
        if baseline_path.exists():
            try:
                baseline = load_baseline(baseline_path)
            except ValueError as exc:
                stderr.write(f"atpu-lint: {exc}\n")
                return 2
            for fp in baseline_notes_missing(baseline):
                stderr.write(
                    f"atpu-lint: warning: baseline entry {fp} has no tracking "
                    "note (policy: every seeded entry says what tracks its "
                    "cleanup)\n"
                )
        elif args.baseline:
            stderr.write(f"atpu-lint: no such baseline: {baseline_path}\n")
            return 2

    project = Project(root=root)
    runner = Runner(rules, project, baseline)
    paths = [Path(p) for p in (args.paths or DEFAULT_PATHS)]
    try:
        report = runner.run(paths)
    except (FileNotFoundError, ValueError) as exc:
        stderr.write(f"{exc}\n")
        return 2

    for warning in report.warnings:
        stderr.write(f"atpu-lint: warning: {warning}\n")

    if args.write_baseline:
        count = write_baseline(baseline_path, report.diagnostics)
        stderr.write(f"atpu-lint: wrote {count} entries to {baseline_path}\n")
        return 0

    if args.format == "json":
        _render_json(report, stdout)
    else:
        _render_text(report, stdout)
    return report.exit_code
