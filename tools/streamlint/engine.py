"""The streamlint rule engine.

Responsibilities split cleanly:

* :class:`SourceFile` — one parsed python file: source text, AST, and
  the ``# streamlint: disable=...`` suppression map (extracted with
  :mod:`tokenize` so ``#`` inside string literals never confuses it).
* :class:`Project` — the analysis root plus a lazy parse index keyed by
  repo-relative posix paths.  Rules pull cross-file targets (the three
  engine modules, the campaign layer, the docs table) through it on
  demand, so scanning ``benchmarks/`` alone still checks project-level
  contracts against ``src/``.
* :class:`Config` — where the contract-bearing files live.  Tests point
  it at fixture trees; the defaults match this repo's layout.
* :func:`run_analysis` — collect diagnostics from every registered
  rule, apply suppressions, append the engine's own hygiene findings
  (SL001 unjustified / SL002 unused suppressions), and wrap the lot in
  an :class:`Analysis` with a JSON-serializable report.

Rule modules register themselves via the :func:`rule` decorator at
import time; :mod:`tools.streamlint.rules` imports them all.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import json
import re
import sys
import tokenize
from pathlib import Path
from typing import Callable, Iterable, Iterator

#: ``# streamlint: disable=SL101,SL403 -- optional justification``
_SUPPRESS_RE = re.compile(
    r"#\s*streamlint:\s*disable=([A-Za-z0-9_,\s]+?)(?:\s+--\s*(\S.*?))?\s*$")


@dataclasses.dataclass(frozen=True)
class Diagnostic:
    """One finding: a rule id anchored to a file:line."""

    rule: str
    file: str
    line: int
    message: str
    suppressed: bool = False
    justified: bool = False

    def format(self) -> str:
        tag = "  [suppressed]" if self.suppressed else ""
        return f"{self.file}:{self.line}: {self.rule} {self.message}{tag}"

    def as_dict(self) -> dict[str, object]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class Suppression:
    """A parsed suppression comment and the line range it covers."""

    rules: frozenset[str]
    comment_line: int
    target_line: int
    justification: str | None
    used: bool = False


class SourceFile:
    """A parsed python file plus its suppression map."""

    def __init__(self, path: str, text: str) -> None:
        self.path = path
        self.text = text
        self.tree = ast.parse(text, filename=path)
        self.suppressions = _extract_suppressions(text)

    def suppression_for(self, rule_id: str, line: int) -> Suppression | None:
        for sup in self.suppressions:
            if sup.target_line == line and rule_id in sup.rules:
                return sup
        return None


def _extract_suppressions(text: str) -> list[Suppression]:
    sups: list[Suppression] = []
    try:
        tokens = list(tokenize.generate_tokens(io.StringIO(text).readline))
    except (tokenize.TokenError, IndentationError):
        return sups
    lines = text.splitlines()
    for tok in tokens:
        if tok.type != tokenize.COMMENT:
            continue
        m = _SUPPRESS_RE.search(tok.string)
        if not m:
            continue
        rules = frozenset(
            r.strip() for r in m.group(1).split(",") if r.strip())
        line = tok.start[0]
        code_before = lines[line - 1][: tok.start[1]].strip() \
            if line - 1 < len(lines) else ""
        # A trailing comment guards its own line; a comment alone on a
        # line guards the next code line (justifications may wrap onto
        # further comment lines).
        target = line
        if not code_before:
            target = line + 1
            while target <= len(lines) and (
                    not lines[target - 1].strip()
                    or lines[target - 1].lstrip().startswith("#")):
                target += 1
        sups.append(Suppression(rules=rules, comment_line=line,
                                target_line=target,
                                justification=m.group(2)))
    return sups


@dataclasses.dataclass
class Config:
    """Where the contract-bearing files live, relative to the root."""

    heap_engine: str = "src/repro/core/simulator.py"
    vectorized_engine: str = "src/repro/core/vectorized.py"
    jax_engine: str = "src/repro/core/jax_engine.py"
    #: the module whose jitted code SL302/SL303 scan (the whole-run
    #: device program)
    jax_program: str = "src/repro/core/jax_device_loop.py"
    campaign: str = "src/repro/core/campaign.py"
    bench_common: str = "benchmarks/common.py"
    parity_constants: str = "src/repro/core/parity.py"
    #: every doc carrying ``band:<key>`` parity-table rows (SL501/SL502)
    band_docs: tuple[str, ...] = ("docs/engines.md", "docs/chaos.md")
    parity_tests: tuple[str, ...] = (
        "tests/test_engine_parity.py", "tests/test_multi_tenant.py",
        "tests/test_chaos.py")
    #: path prefixes whose modules count as deterministic engine paths
    determinism_scope: tuple[str, ...] = ("src/repro/core/",)
    #: names that wrap a function into a jitted kernel in jax_program
    jit_wrappers: tuple[str, ...] = ("x64", "jit")


class Project:
    """Analysis root + lazy parse index over repo-relative paths."""

    def __init__(self, root: Path, config: Config | None = None) -> None:
        self.root = Path(root)
        self.config = config or Config()
        self._files: dict[str, SourceFile | None] = {}
        self.parse_errors: list[Diagnostic] = []

    def file(self, relpath: str) -> SourceFile | None:
        """Parse (and cache) ``root/relpath``; None if absent/bad."""
        if relpath not in self._files:
            full = self.root / relpath
            sf: SourceFile | None = None
            if full.is_file():
                try:
                    sf = SourceFile(relpath,
                                    full.read_text(encoding="utf-8"))
                except SyntaxError as exc:
                    self.parse_errors.append(Diagnostic(
                        rule="SL900", file=relpath,
                        line=exc.lineno or 1,
                        message=f"syntax error: {exc.msg}"))
            self._files[relpath] = sf
        return self._files[relpath]

    def text(self, relpath: str) -> str | None:
        """Raw text of a (possibly non-python) file, or None."""
        full = self.root / relpath
        if not full.is_file():
            return None
        return full.read_text(encoding="utf-8")

    def scan(self, paths: Iterable[str]) -> list[SourceFile]:
        """Parse every ``*.py`` under the given root-relative paths."""
        out: list[SourceFile] = []
        for rel in _collect_py(self.root, paths):
            sf = self.file(rel)
            if sf is not None:
                out.append(sf)
        return out


def _collect_py(root: Path, paths: Iterable[str]) -> Iterator[str]:
    seen: list[str] = []
    for p in paths:
        full = (root / p).resolve()
        if full.is_file() and full.suffix == ".py":
            cands = [full]
        elif full.is_dir():
            cands = sorted(full.rglob("*.py"))
        else:
            cands = []
        for c in cands:
            rel = c.relative_to(root.resolve()).as_posix()
            if rel not in seen:
                seen.append(rel)
                yield rel


# ---------------------------------------------------------------------------
# rule registry

RuleFn = Callable[[Project, list[SourceFile]], Iterable[Diagnostic]]

#: rule id -> (one-line description, check function)
RULES: dict[str, tuple[str, RuleFn]] = {}


def rule(rule_id: str, description: str) -> Callable[[RuleFn], RuleFn]:
    """Register ``fn`` as the checker behind ``rule_id``."""

    def deco(fn: RuleFn) -> RuleFn:
        RULES[rule_id] = (description, fn)
        return fn

    return deco


def _load_rules() -> None:
    # Imported lazily so ``engine`` itself stays import-cycle free.
    from tools.streamlint import rules  # noqa: F401


# ---------------------------------------------------------------------------
# analysis driver


@dataclasses.dataclass
class Analysis:
    """The outcome of one streamlint run."""

    root: str
    files_scanned: list[str]
    diagnostics: list[Diagnostic]

    @property
    def failures(self) -> list[Diagnostic]:
        return [d for d in self.diagnostics if not d.suppressed]

    @property
    def exit_code(self) -> int:
        return 1 if self.failures else 0

    def report(self) -> dict[str, object]:
        counts: dict[str, int] = {}
        for d in self.failures:
            counts[d.rule] = counts.get(d.rule, 0) + 1
        return {
            "version": 1,
            "root": self.root,
            "files_scanned": self.files_scanned,
            "rules": {rid: desc for rid, (desc, _) in sorted(RULES.items())},
            "diagnostics": [d.as_dict() for d in self.diagnostics],
            "counts": counts,
            "exit_code": self.exit_code,
        }

    def write_json(self, path: str | Path) -> None:
        Path(path).write_text(
            json.dumps(self.report(), indent=2) + "\n", encoding="utf-8")


def run_analysis(root: str | Path, paths: Iterable[str] = ("src",),
                 config: Config | None = None,
                 only: Iterable[str] | None = None) -> Analysis:
    """Run every registered rule over the tree rooted at ``root``.

    ``paths`` are root-relative files/directories to scan for per-file
    rules; project-level rules additionally pull their fixed targets
    (``config``) through the parse index regardless of ``paths``.
    ``only`` restricts to a subset of rule ids (used by fixture tests).
    """
    _load_rules()
    project = Project(Path(root), config)
    scanned = project.scan(list(paths))
    wanted = set(only) if only is not None else None

    raw: list[Diagnostic] = []
    for rule_id, (_, fn) in sorted(RULES.items()):
        if wanted is not None and rule_id not in wanted:
            continue
        raw.extend(fn(project, scanned))
    raw.extend(project.parse_errors)

    final: list[Diagnostic] = []
    for diag in raw:
        sf = project.file(diag.file) if diag.file.endswith(".py") else None
        sup = sf.suppression_for(diag.rule, diag.line) if sf else None
        if sup is not None:
            sup.used = True
            final.append(dataclasses.replace(
                diag, suppressed=True,
                justified=sup.justification is not None))
        else:
            final.append(diag)

    # Engine-level hygiene over every suppression comment encountered.
    hygiene = wanted is None or wanted & {"SL001", "SL002"}
    if hygiene:
        for sf in scanned:
            for sup in sf.suppressions:
                ids = ",".join(sorted(sup.rules))
                if (wanted is None or "SL001" in wanted) \
                        and sup.justification is None:
                    final.append(Diagnostic(
                        rule="SL001", file=sf.path, line=sup.comment_line,
                        message=(f"suppression of {ids} has no "
                                 "justification; append ' -- <reason>'")))
                if (wanted is None or "SL002" in wanted) and not sup.used:
                    final.append(Diagnostic(
                        rule="SL002", file=sf.path, line=sup.comment_line,
                        message=(f"suppression of {ids} matched no "
                                 "diagnostic on its line; remove it")))

    final.sort(key=lambda d: (d.file, d.line, d.rule))
    return Analysis(root=str(project.root),
                    files_scanned=[sf.path for sf in scanned],
                    diagnostics=final)


@rule("SL001", "suppression comments must carry a ' -- reason' "
               "justification")
def _sl001_doc_only(project: Project,
                    scanned: list[SourceFile]) -> Iterable[Diagnostic]:
    # Emitted by the engine itself after suppression accounting; the
    # registration here exists so the rule shows up in --list-rules and
    # the JSON report's rule catalog.
    return ()


@rule("SL002", "suppressions must actually suppress a diagnostic")
def _sl002_doc_only(project: Project,
                    scanned: list[SourceFile]) -> Iterable[Diagnostic]:
    return ()


def main(argv: list[str] | None = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m tools.streamlint",
        description="AST-level engine-contract analysis for this repo.")
    parser.add_argument("paths", nargs="*", default=["src", "benchmarks"],
                        help="root-relative files/dirs to scan "
                             "(default: src benchmarks)")
    parser.add_argument("--root", default=".",
                        help="analysis root (default: cwd)")
    parser.add_argument("--json", metavar="FILE", default=None,
                        help="also write the full JSON report here")
    parser.add_argument("--list-rules", action="store_true",
                        help="print the rule catalog and exit")
    args = parser.parse_args(argv)

    _load_rules()
    if args.list_rules:
        for rid, (desc, _) in sorted(RULES.items()):
            print(f"{rid}  {desc}")
        return 0

    analysis = run_analysis(args.root, args.paths or ["src", "benchmarks"])
    for diag in analysis.diagnostics:
        if not diag.suppressed:
            print(diag.format())
    n_sup = sum(1 for d in analysis.diagnostics if d.suppressed)
    print(f"streamlint: {len(analysis.files_scanned)} files, "
          f"{len(analysis.failures)} finding(s), "
          f"{n_sup} suppressed", file=sys.stderr)
    if args.json:
        analysis.write_json(args.json)
    return analysis.exit_code
