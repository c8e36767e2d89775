"""Scan driver: file discovery, checker dispatch, noqa + baseline filters."""

from __future__ import annotations

import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Iterable, Optional

from .baseline import Baseline, fingerprint
from .core import FileContext, Finding, ProjectContext, Severity, parse_noqa
from .registry import all_checkers

# What `python scripts/cdt_lint.py` gates by default: the package plus
# the executable entry points. tests/ are exempt (they exercise the
# hazards on purpose); the linter does not lint itself or its fixtures.
DEFAULT_SCAN_PATHS = (
    "comfyui_distributed_tpu",
    "scripts",
    "chip_smoke.py",
    "__graft_entry__.py",
)

_EXCLUDE_DIRS = {"__pycache__", "web", ".git", ".cdt"}


def discover_files(root: str, paths: Iterable[str]) -> list[str]:
    """Expand scan paths to repo-relative .py files, sorted (CDT004
    practices what it preaches)."""
    out: set[str] = set()
    for rel in paths:
        abs_path = os.path.join(root, rel)
        if os.path.isfile(abs_path):
            if rel.endswith(".py"):
                out.add(rel.replace(os.sep, "/"))
            continue
        for dirpath, dirnames, filenames in os.walk(abs_path):
            dirnames[:] = sorted(d for d in dirnames if d not in _EXCLUDE_DIRS)
            for fname in filenames:
                if not fname.endswith(".py"):
                    continue
                full = os.path.join(dirpath, fname)
                out.add(os.path.relpath(full, root).replace(os.sep, "/"))
    return sorted(out)


@dataclass
class LintResult:
    findings: list[Finding] = field(default_factory=list)  # actionable (gate fails)
    baselined: list[Finding] = field(default_factory=list)
    suppressed: list[Finding] = field(default_factory=list)
    stale_baseline: list[str] = field(default_factory=list)  # fingerprints
    parse_errors: list[str] = field(default_factory=list)
    files_scanned: int = 0

    @property
    def ok(self) -> bool:
        return not self.findings and not self.stale_baseline and not self.parse_errors

    def as_json(self) -> dict:
        return {
            "ok": self.ok,
            "files_scanned": self.files_scanned,
            "findings": [f.as_json() for f in self.findings],
            "baselined": [f.as_json() for f in self.baselined],
            "suppressed": [f.as_json() for f in self.suppressed],
            "stale_baseline": list(self.stale_baseline),
            "parse_errors": list(self.parse_errors),
        }


def _line_text(ctx_by_path: dict[str, FileContext], finding: Finding) -> str:
    ctx = ctx_by_path.get(finding.path)
    return ctx.line_text(finding.line) if ctx else ""


def run_lint(
    root: str,
    paths: Optional[Iterable[str]] = None,
    baseline: Optional[Baseline] = None,
    select: Optional[set[str]] = None,
) -> LintResult:
    """Run every registered checker over ``paths`` (repo-relative).

    ``baseline`` entries filter matching findings out of the failure
    set; entries no fresh finding matches are reported as stale.
    ``select`` restricts to a subset of checker codes (tests use this).
    """
    result = LintResult()
    checkers = all_checkers()
    if select is not None:
        checkers = {c: info for c, info in checkers.items() if c in select}

    contexts: list[FileContext] = []
    for rel in discover_files(root, paths or DEFAULT_SCAN_PATHS):
        abs_path = os.path.join(root, rel)
        try:
            with open(abs_path, "r", encoding="utf-8") as fh:
                source = fh.read()
            ctx = FileContext.parse(rel, source)
        except (OSError, SyntaxError, ValueError) as exc:
            result.parse_errors.append(f"{rel}: {exc}")
            continue
        contexts.append(ctx)
    result.files_scanned = len(contexts)
    ctx_by_path = {c.path: c for c in contexts}

    raw: list[Finding] = []
    for ctx in contexts:
        for info in checkers.values():
            if info.scope != "file":
                continue
            raw.extend(info.fn(ctx))
    project = ProjectContext(root=root, files=contexts)
    for info in checkers.values():
        if info.scope == "project":
            raw.extend(info.fn(project))

    raw.sort(key=lambda f: (f.path, f.line, f.col, f.code))

    # noqa suppression (per-file, per-line, optional per-code)
    noqa_by_path = {c.path: parse_noqa(c.lines) for c in contexts}
    survivors: list[Finding] = []
    for f in raw:
        codes = noqa_by_path.get(f.path, {}).get(f.line, "missing")
        if codes is None or (codes != "missing" and f.code in codes):
            result.suppressed.append(f)
        else:
            survivors.append(f)

    # baseline matching: identical (path, code, stripped-line) findings
    # get per-occurrence indices so the fingerprints stay stable
    baseline = baseline or Baseline(path="")
    occurrence: dict[tuple[str, str, str], int] = defaultdict(int)
    matched_fps: set[str] = set()
    for f in survivors:
        text = _line_text(ctx_by_path, f).strip()
        key = (f.path, f.code, text)
        fp = fingerprint(f, text, occurrence[key])
        occurrence[key] += 1
        if fp in baseline:
            matched_fps.add(fp)
            result.baselined.append(f)
        else:
            result.findings.append(f)

    # Stale detection only covers entries a THIS scan could have
    # re-produced: a partial scan (explicit paths, --select) must not
    # report out-of-scope grandfathered entries as stale — and
    # --update-baseline must not silently drop them.
    scanned_paths = set(ctx_by_path)
    active_codes = set(checkers)
    in_scope = {
        fp
        for fp, entry in baseline.entries.items()
        if entry.get("path") in scanned_paths and entry.get("code") in active_codes
    }
    result.stale_baseline = sorted(in_scope - matched_fps)
    return result


def compute_fingerprints(
    root: str,
    result_findings: list[Finding],
    already_baselined: Optional[list[Finding]] = None,
) -> dict[str, dict]:
    """Baseline entries for ``--update-baseline``: re-reads sources to
    recover line text for each finding.

    ``already_baselined`` findings participate in occurrence numbering
    (they did in :func:`run_lint` too) but produce no entries — without
    them, a new finding on a line identical to a baselined one would be
    fingerprinted at occurrence 0, collide with the existing entry, and
    the update would never converge.
    """
    sources: dict[str, list[str]] = {}
    occurrence: dict[tuple[str, str, str], int] = defaultdict(int)
    entries: dict[str, dict] = {}
    new_ids = {id(f) for f in result_findings}
    merged = list(result_findings) + list(already_baselined or [])
    for f in sorted(merged, key=lambda f: (f.path, f.line, f.col, f.code)):
        if f.path not in sources:
            try:
                with open(os.path.join(root, f.path), "r", encoding="utf-8") as fh:
                    sources[f.path] = fh.read().splitlines()
            except OSError:
                sources[f.path] = []
        lines = sources[f.path]
        text = lines[f.line - 1].strip() if 0 < f.line <= len(lines) else ""
        key = (f.path, f.code, text)
        fp = fingerprint(f, text, occurrence[key])
        occurrence[key] += 1
        if id(f) not in new_ids:
            continue
        entries[fp] = {
            "code": f.code,
            "path": f.path,
            "line": f.line,
            "text": text,
            "justification": "TODO: justify or fix (baseline policy: shrink-only)",
        }
    return entries


def render_text(result: LintResult, verbose: bool = False) -> str:
    lines: list[str] = []
    for err in result.parse_errors:
        lines.append(f"PARSE ERROR: {err}")
    for f in result.findings:
        lines.append(f.render())
    for fp in result.stale_baseline:
        lines.append(f"STALE BASELINE ENTRY: {fp} (fixed finding still listed; remove it)")
    if verbose:
        for f in result.baselined:
            lines.append(f"baselined: {f.render()}")
        for f in result.suppressed:
            lines.append(f"suppressed: {f.render()}")
    n_err = sum(1 for f in result.findings if f.severity is Severity.ERROR)
    n_warn = len(result.findings) - n_err
    lines.append(
        f"cdt-lint: {result.files_scanned} files scanned, "
        f"{n_err} error(s), {n_warn} warning(s), "
        f"{len(result.baselined)} baselined, {len(result.suppressed)} suppressed, "
        f"{len(result.stale_baseline)} stale baseline entr(y/ies)"
    )
    return "\n".join(lines)


__all__ = [
    "DEFAULT_SCAN_PATHS",
    "LintResult",
    "discover_files",
    "run_lint",
    "render_text",
    "compute_fingerprints",
]
