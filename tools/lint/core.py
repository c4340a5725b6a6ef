"""impala-lint framework core: file model, findings, annotations, baseline.

The suite (tools/lint/) is one AST walk shared by four checkers
(docs/STATIC_ANALYSIS.md has the rule catalog):

- ``thread-safety``  (threads.py)  cross-thread attribute guarding + the
  lock-acquisition-order graph;
- ``jit-boundary``   (jitb.py)     host syncs inside jitted code / hot
  loops + donate_argnums liveness;
- ``shm-lifecycle``  (shm.py)      SharedMemory create/close/unlink
  pairing on all exit paths;
- ``telemetry``      (metrics.py)  metric/trace name grammar (the former
  tools/check_metric_names.py, folded in).

Static on purpose, like check_metric_names was: the suite runs from
tier-1 (tests/test_lint.py) without spawning pools or initializing jax,
and it sees dead call sites too — a race seeded in a rarely-taken branch
still fails CI.

Two suppression mechanisms, both requiring a human-written reason:

- inline annotations — a ``# lint: <directive>`` comment on the
  offending line.  Grammar (one or more comma-separated directives):

    ``allow(<rule>)``       suppress findings of <rule> (or a whole
                            checker, e.g. ``allow(thread-safety)``) on
                            this line;
    ``guarded-by(<lock>)``  declare the lock guarding an attribute (on
                            its ``self.x = ...`` line) or held around a
                            whole method (on its ``def`` line);
                            ``guarded-by(gil)`` declares a single
                            bytecode-atomic flag/counter;
    ``hot-loop``            mark a ``def`` as a throughput hot loop the
                            jit-boundary checker must keep free of host
                            syncs.

- the baseline file (tools/lint/baseline.txt) — grandfathered findings,
  one per line: ``<rule> <key> <justification...>``.  Keys are stable
  (no line numbers), so the baseline survives unrelated edits; an entry
  that no longer matches anything is reported as stale.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

DEFAULT_BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "baseline.txt"
)

# Scanned by default: the package. Tools and tests are excluded
# (fixtures under tests/lint_fixtures/ carry seeded violations by
# design).
DEFAULT_ROOTS = ("torched_impala_tpu",)


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation at a concrete source location.

    ``rule`` is ``<checker>/<rule-name>``; ``key`` is the stable
    baseline identity (path + symbol, never a line number) so a
    grandfathered finding stays suppressed while the file shifts."""

    rule: str
    path: str  # repo-relative, forward slashes
    line: int
    message: str
    key: str = ""

    @property
    def baseline_key(self) -> str:
        return self.key or self.path

    def format(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


@dataclasses.dataclass(frozen=True)
class Directive:
    name: str  # "allow" | "guarded-by" | "hot-loop"
    arg: str = ""


_LINT_COMMENT = re.compile(r"#\s*lint:\s*(.+)$")
_DIRECTIVE = re.compile(r"^([a-z-]+)(?:\(([^)]*)\))?$")


def parse_directives(line: str) -> List[Directive]:
    """Directives carried by one source line (empty when none)."""
    m = _LINT_COMMENT.search(line)
    if not m:
        return []
    out: List[Directive] = []
    for part in m.group(1).split(","):
        part = part.strip()
        if not part:
            continue
        dm = _DIRECTIVE.match(part)
        if dm and dm.group(1) in ("allow", "guarded-by", "hot-loop"):
            out.append(Directive(dm.group(1), (dm.group(2) or "").strip()))
        else:
            # A malformed directive is itself a finding (a typo'd
            # annotation must not silently fail open/closed).
            out.append(Directive("malformed", part))
    return out


class SourceFile:
    """One parsed file handed to every checker: text, lines, AST, and
    the per-line ``# lint:`` directives."""

    def __init__(self, path: str, rel: str, text: str) -> None:
        self.path = path
        self.rel = rel.replace(os.sep, "/")
        self.text = text
        self.lines = text.splitlines()
        self.annotations: Dict[int, List[Directive]] = {}
        for i, line in enumerate(self.lines, 1):
            ds = parse_directives(line)
            if ds:
                self.annotations[i] = ds
        try:
            self.tree: Optional[ast.AST] = ast.parse(text, filename=path)
            self.parse_error: Optional[SyntaxError] = None
        except SyntaxError as e:  # surfaced as a framework finding
            self.tree = None
            self.parse_error = e

    def directives(self, line: int, name: str) -> List[Directive]:
        return [d for d in self.annotations.get(line, []) if d.name == name]

    def allows(self, line: int, rule: str) -> bool:
        """True when an ``allow(...)`` on `line` covers `rule` (exact
        rule, its checker prefix, or ``all``)."""
        for d in self.directives(line, "allow"):
            if d.arg in ("all", rule) or rule.startswith(d.arg + "/"):
                return True
        return False


def _iter_py_files(root: str, roots: Sequence[str]) -> Iterable[str]:
    for entry in roots:
        path = os.path.join(root, entry)
        if os.path.isfile(path):
            yield path
        elif os.path.isdir(path):
            for dirpath, dirnames, filenames in os.walk(path):
                dirnames[:] = [d for d in dirnames if d != "__pycache__"]
                for f in sorted(filenames):
                    if f.endswith(".py"):
                        yield os.path.join(dirpath, f)


def load_files(
    root: str = REPO, roots: Sequence[str] = DEFAULT_ROOTS
) -> List[SourceFile]:
    files = []
    for path in sorted(_iter_py_files(root, roots)):
        with open(path, encoding="utf-8") as f:
            text = f.read()
        files.append(SourceFile(path, os.path.relpath(path, root), text))
    return files


def framework_findings(files: Sequence[SourceFile]) -> List[Finding]:
    """Findings about the lint inputs themselves: unparsable files and
    malformed ``# lint:`` annotations."""
    out: List[Finding] = []
    for sf in files:
        if sf.parse_error is not None:
            out.append(
                Finding(
                    rule="framework/parse-error",
                    path=sf.rel,
                    line=sf.parse_error.lineno or 0,
                    message=f"file does not parse: {sf.parse_error.msg}",
                    key=f"{sf.rel}::parse",
                )
            )
        for lineno, ds in sf.annotations.items():
            for d in ds:
                if d.name == "malformed":
                    out.append(
                        Finding(
                            rule="framework/bad-annotation",
                            path=sf.rel,
                            line=lineno,
                            message=(
                                f"unrecognized lint directive {d.arg!r} "
                                "(expected allow(<rule>), "
                                "guarded-by(<lock>|gil), or hot-loop)"
                            ),
                            key=f"{sf.rel}::annotation:{d.arg}",
                        )
                    )
    return out


# ---- baseline -------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BaselineEntry:
    rule: str
    key: str
    justification: str
    line: int  # line in the baseline file (for stale reports)


def load_baseline(path: Optional[str]) -> List[BaselineEntry]:
    """Parse the suppression file. Format per non-comment line:
    ``<rule> <key> <one-line justification>`` — the justification is
    REQUIRED (a baseline without a reason is just a muted bug)."""
    if path is None or not os.path.exists(path):
        return []
    entries: List[BaselineEntry] = []
    with open(path, encoding="utf-8") as f:
        for lineno, raw in enumerate(f, 1):
            line = raw.strip()
            if not line or line.startswith("#"):
                continue
            parts = line.split(None, 2)
            if len(parts) < 3:
                raise ValueError(
                    f"{path}:{lineno}: baseline entry needs "
                    f"'<rule> <key> <justification>', got {line!r}"
                )
            entries.append(
                BaselineEntry(parts[0], parts[1], parts[2], lineno)
            )
    return entries


@dataclasses.dataclass
class LintResult:
    findings: List[Finding]  # active (not baselined)
    suppressed: List[Tuple[Finding, BaselineEntry]]
    stale_baseline: List[BaselineEntry]

    @property
    def ok(self) -> bool:
        return not self.findings


def apply_baseline(
    findings: Sequence[Finding], entries: Sequence[BaselineEntry]
) -> LintResult:
    by_id = {(e.rule, e.key): e for e in entries}
    used = set()
    active: List[Finding] = []
    suppressed: List[Tuple[Finding, BaselineEntry]] = []
    for f in findings:
        e = by_id.get((f.rule, f.baseline_key))
        if e is not None:
            used.add((e.rule, e.key))
            suppressed.append((f, e))
        else:
            active.append(f)
    stale = [e for e in entries if (e.rule, e.key) not in used]
    return LintResult(active, suppressed, stale)


# ---- runner ---------------------------------------------------------------


def apply_inline_allows(
    files: Sequence[SourceFile], findings: Sequence[Finding]
) -> List[Finding]:
    """Drop findings whose line carries a covering ``allow(...)``
    directive. run_all applies this; fixture-driven tests calling a
    checker directly should too."""
    by_file = {sf.rel: sf for sf in files}
    return [
        f
        for f in findings
        if not (
            f.path in by_file and by_file[f.path].allows(f.line, f.rule)
        )
    ]


def checkers(
    hot_loop_depth: int = 0,
) -> Dict[str, Callable[[Sequence[SourceFile]], List[Finding]]]:
    # Imported lazily so `from tools.lint.core import Finding` never
    # drags in every checker (the shim imports metrics only).
    import functools

    from tools.lint import (
        donation,
        dtypes,
        jitb,
        metrics,
        sharding,
        shm,
        threads,
    )

    return {
        "thread-safety": threads.check,
        "jit-boundary": functools.partial(
            jitb.check, hot_loop_depth=hot_loop_depth
        ),
        "shm-lifecycle": shm.check,
        "telemetry": metrics.check,
        "sharding": sharding.check,
        "donation": donation.check,
        "dtype": dtypes.check,
    }


def run_all(
    root: str = REPO,
    *,
    roots: Sequence[str] = DEFAULT_ROOTS,
    baseline_path: Optional[str] = DEFAULT_BASELINE,
    only: Optional[Sequence[str]] = None,
    hot_loop_depth: int = 0,
) -> LintResult:
    """Walk `roots` under `root`, run the checkers (all by default),
    apply the baseline. Inline ``allow(...)`` suppression is applied by
    the framework here, so checkers never reimplement it."""
    files = load_files(root, roots)
    findings = framework_findings(files)
    table = checkers(hot_loop_depth)
    names = list(table) if only is None else list(only)
    for name in names:
        if name not in table:
            raise KeyError(
                f"unknown checker {name!r}; have {sorted(table)}"
            )
        findings.extend(table[name](files))
    kept = apply_inline_allows(files, findings)
    findings = sorted(kept, key=lambda f: (f.path, f.line, f.rule))
    return apply_baseline(findings, load_baseline(baseline_path))
