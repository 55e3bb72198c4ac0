"""SL2xx — cache-key completeness for the resumable campaign cache.

PR 2's ``LegacyCacheError`` made a stale fingerprint *loud*; these
rules make the underlying mistake impossible to commit.  A knob added
to ``SimParams`` or ``ExperimentSpec`` that does not reach the
fingerprint/cell key would let two different configurations share a
cache entry — silent result poisoning across resumes.

* SL201 — ``SimParams`` field not covered by
  ``campaign.params_fingerprint``.  Covering the whole ``__dict__``
  (or ``dataclasses.asdict``/``fields``/``astuple``) is
  field-complete by construction and passes outright.
* SL202 — ``CellSpec`` field that never flows into ``cell_key``
  (directly, or via the ``cell.experiment()`` expansion).
* SL203 — ``ExperimentSpec`` field not threaded through the
  ``ExperimentSpec(...)`` construction inside ``CellSpec.experiment``
  (a spec knob campaigns could never set — and therefore never key).
* SL204 — a cache-key builder that keys on the *requested* engine
  instead of the *resolved* execution path.  ``run_many`` downgrades
  unsupported jax cells to the vectorized engine, and the device
  program's regime gate sends declined cells there too; a key built
  before that resolution caches one path's numbers
  under another's name (poisoning later runs of the other path) and
  forks them from the identical cell.  ``campaign.cell_key`` and
  ``benchmarks.common.resolve_path`` must route through
  ``campaign.resolved_path``, and ``benchmarks.common.resolve_engine``
  through ``campaign.resolved_engine``.
"""

from __future__ import annotations

import ast
from typing import Iterable

from tools.streamlint.engine import (Diagnostic, Project, SourceFile,
                                     rule)
from tools.streamlint.rules._helpers import (attr_reads, calls_to,
                                             dataclass_fields, dotted,
                                             find_class, find_func,
                                             kwarg_names)

#: accessing any of these on the params argument covers every field
_WHOLESALE = {"__dict__"}
_WHOLESALE_CALLS = {"asdict", "astuple", "fields", "vars"}


def _spec_fields(project: Project, name: str) -> dict[str, int] | None:
    heap = project.file(project.config.heap_engine)
    if heap is None:
        return None
    cls = find_class(heap.tree, name)
    return dataclass_fields(cls) if cls is not None else None


def _covers_wholesale(func: ast.FunctionDef, arg: str) -> bool:
    if attr_reads(func, arg) & _WHOLESALE:
        return True
    for node in ast.walk(func):
        if isinstance(node, ast.Call):
            d = dotted(node.func) or ""
            if d.split(".")[-1] in _WHOLESALE_CALLS and any(
                    isinstance(a, ast.Name) and a.id == arg
                    for a in node.args):
                return True
    return False


@rule("SL201", "every SimParams field must flow into "
               "campaign.params_fingerprint")
def sl201(project: Project,
          scanned: list[SourceFile]) -> Iterable[Diagnostic]:
    camp = project.file(project.config.campaign)
    fields = _spec_fields(project, "SimParams")
    if camp is None or fields is None:
        return
    func = find_func(camp.tree, "params_fingerprint")
    if func is None or not func.args.args:
        return
    arg = func.args.args[0].arg
    if _covers_wholesale(func, arg):
        return
    covered = attr_reads(func, arg)
    for field in sorted(set(fields) - covered):
        yield Diagnostic(
            rule="SL201", file=camp.path, line=func.lineno,
            message=(f"params_fingerprint does not cover SimParams "
                     f"field {field!r}; a campaign varying it would "
                     f"reuse stale cache entries"))


@rule("SL202", "every CellSpec field must flow into campaign.cell_key")
def sl202(project: Project,
          scanned: list[SourceFile]) -> Iterable[Diagnostic]:
    camp = project.file(project.config.campaign)
    if camp is None:
        return
    cls = find_class(camp.tree, "CellSpec")
    func = find_func(camp.tree, "cell_key")
    if cls is None or func is None or not func.args.args:
        return
    fields = dataclass_fields(cls)
    arg = func.args.args[0].arg
    covered = attr_reads(func, arg)
    if "experiment" in covered:
        # cell.experiment() expands the cell into an ExperimentSpec;
        # whatever that expansion reads off self is covered too.
        exp = find_func(cls, "experiment")
        if exp is not None:
            covered |= attr_reads(exp, "self")
    for field in sorted(set(fields) - covered):
        yield Diagnostic(
            rule="SL202", file=camp.path, line=func.lineno,
            message=(f"cell_key does not cover CellSpec field "
                     f"{field!r}; two cells differing only in it "
                     f"would collide in the cache"))


def _calls_name(func: ast.AST, name: str) -> bool:
    for node in ast.walk(func):
        if isinstance(node, ast.Call):
            d = dotted(node.func) or ""
            if d.split(".")[-1] == name:
                return True
    return False


@rule("SL204", "cache keys must be built from the resolved engine "
               "and path, never the requested engine")
def sl204(project: Project,
          scanned: list[SourceFile]) -> Iterable[Diagnostic]:
    camp = project.file(project.config.campaign)
    if camp is not None:
        func = find_func(camp.tree, "cell_key")
        if func is not None and not _calls_name(func, "resolved_path"):
            yield Diagnostic(
                rule="SL204", file=camp.path, line=func.lineno,
                message=("cell_key never calls resolved_path: a jax "
                         "cell the run_many fallback downgrades to "
                         "vectorized (or the device-program gate "
                         "declines) would be cached under "
                         "the path it did not run, poisoning later "
                         "runs of that path"))
    bench = project.file(project.config.bench_common)
    if bench is not None:
        for name, helper in (("resolve_engine", "resolved_engine"),
                             ("resolve_path", "resolved_path")):
            func = find_func(bench.tree, name)
            if func is not None and not _calls_name(func, helper):
                yield Diagnostic(
                    rule="SL204", file=bench.path, line=func.lineno,
                    message=(f"benchmarks.common.{name} never consults "
                             f"campaign.{helper}: bench cache keys would "
                             f"carry the requested engine or path "
                             f"instead of the one that actually ran"))


@rule("SL203", "every ExperimentSpec field must be threaded through "
               "CellSpec.experiment")
def sl203(project: Project,
          scanned: list[SourceFile]) -> Iterable[Diagnostic]:
    camp = project.file(project.config.campaign)
    fields = _spec_fields(project, "ExperimentSpec")
    if camp is None or fields is None:
        return
    cls = find_class(camp.tree, "CellSpec")
    if cls is None:
        return
    exp = find_func(cls, "experiment")
    if exp is None:
        return
    for call in calls_to(exp, "ExperimentSpec"):
        for field in sorted(set(fields) - kwarg_names(call)):
            yield Diagnostic(
                rule="SL203", file=camp.path, line=call.lineno,
                message=(f"CellSpec.experiment builds ExperimentSpec "
                         f"without {field!r}; campaigns can never set "
                         f"(or cache-key) it"))
