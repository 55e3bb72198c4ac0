"""streamlint's own suite: per-rule good/bad fixture pairs on synthetic
trees, suppression-comment semantics, the CLI/JSON surface, and a
self-check that the live tree is violation-free (modulo justified
suppressions).

Fixture trees mirror the repo layout the default ``Config`` expects
(``src/repro/core/...``), written into ``tmp_path`` — the analyzer
never imports the code under test, so the snippets only have to parse.
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

from tools.streamlint import run_analysis  # noqa: E402

HEAP = "src/repro/core/simulator.py"
VEC = "src/repro/core/vectorized.py"
JAX = "src/repro/core/jax_engine.py"
CAMPAIGN = "src/repro/core/campaign.py"
PARITY = "src/repro/core/parity.py"
DOC = "docs/engines.md"


def lint(tmp_path, tree, paths=("src",), only=None):
    """Write a fixture tree, run the analyzer, return unsuppressed
    diagnostics."""
    for rel, text in tree.items():
        full = tmp_path / rel
        full.parent.mkdir(parents=True, exist_ok=True)
        full.write_text(textwrap.dedent(text))
    analysis = run_analysis(tmp_path, paths, only=only)
    return analysis.failures


def rules_of(diags):
    return {d.rule for d in diags}


# -- SL1xx: engine-contract symmetry ---------------------------------------

HEAP_OK = """
    import dataclasses

    @dataclasses.dataclass
    class RunResult:
        spec: object
        feasible: bool
        infeasible_reason: str = ""
        rtts: object = None
        sim_time: float = 0.0

    class StreamSim:
        def run(self):
            return RunResult(spec=self.spec, feasible=True,
                             rtts=[], sim_time=1.0)

    ENGINES = {}
    ENGINES["heap"] = StreamSim
"""

VEC_OK = """
    class VectorizedStreamSim:
        def _result(self):
            return RunResult(spec=self.spec, feasible=True,
                             rtts=[], sim_time=2.0)

    def run_many(specs):
        return [RunResult(spec=s, feasible=False,
                          infeasible_reason="nope") for s in specs]

    ENGINES = {}
    ENGINES["vectorized"] = VectorizedStreamSim
"""

JAX_OK = """
    class JaxStreamSim(VectorizedStreamSim):
        pass

    ENGINES = {}
    ENGINES["jax"] = JaxStreamSim
"""


def test_sl101_vectorized_missing_heap_field(tmp_path):
    vec_bad = VEC_OK.replace("rtts=[], ", "")
    diags = lint(tmp_path, {HEAP: HEAP_OK, VEC: vec_bad, JAX: JAX_OK},
                 only={"SL101"})
    assert rules_of(diags) == {"SL101"}
    assert "'rtts'" in diags[0].message
    assert not lint(tmp_path, {VEC: VEC_OK}, only={"SL101"})


def test_sl102_heap_missing_vectorized_field(tmp_path):
    vec_bad = VEC_OK.replace("sim_time=2.0", "sim_time=2.0, extra=1")
    diags = lint(tmp_path, {HEAP: HEAP_OK, VEC: vec_bad, JAX: JAX_OK},
                 only={"SL102"})
    assert rules_of(diags) == {"SL102"}
    assert "'extra'" in diags[0].message


def test_sl103_field_nobody_populates(tmp_path):
    heap_bad = HEAP_OK.replace(
        "sim_time: float = 0.0",
        "sim_time: float = 0.0\n        ghost: int = 0")
    diags = lint(tmp_path, {HEAP: heap_bad, VEC: VEC_OK, JAX: JAX_OK},
                 only={"SL103"})
    assert rules_of(diags) == {"SL103"}
    assert "'ghost'" in diags[0].message
    # infeasible_reason is exempt: feasible constructions never pass it
    assert not lint(tmp_path, {HEAP: HEAP_OK}, only={"SL103"})


def test_sl104_jax_neither_subclasses_nor_constructs(tmp_path):
    jax_bad = """
        class JaxStreamSim:
            pass

        ENGINES = {}
        ENGINES["jax"] = JaxStreamSim
    """
    diags = lint(tmp_path, {HEAP: HEAP_OK, VEC: VEC_OK, JAX: jax_bad},
                 only={"SL104"})
    assert rules_of(diags) == {"SL104"}
    # subclassing the vectorized engine is the sanctioned handling
    assert not lint(tmp_path, {JAX: JAX_OK}, only={"SL104"})


def test_sl104_jax_incomplete_own_construction(tmp_path):
    jax_bad = """
        class JaxStreamSim:
            def run(self):
                return RunResult(spec=self.spec, feasible=True,
                                 sim_time=3.0)

        ENGINES = {}
        ENGINES["jax"] = JaxStreamSim
    """
    diags = lint(tmp_path, {HEAP: HEAP_OK, VEC: VEC_OK, JAX: jax_bad},
                 only={"SL104"})
    assert rules_of(diags) == {"SL104"}
    assert "'rtts'" in diags[0].message


# -- SL2xx: cache-key completeness -----------------------------------------

SIM_SPECS = """
    import dataclasses

    @dataclasses.dataclass
    class SimParams:
        seed: int = 0
        window_bytes: int = 1024

    @dataclasses.dataclass
    class ExperimentSpec:
        pattern: str = "work_sharing"
        arch: str = "dts"
"""

CAMPAIGN_OK = """
    import dataclasses

    def params_fingerprint(params):
        return repr(sorted(params.__dict__.items()))

    @dataclasses.dataclass
    class CellSpec:
        pattern: str = "work_sharing"
        arch: str = "dts"

        def experiment(self):
            return ExperimentSpec(pattern=self.pattern, arch=self.arch)

    def cell_key(cell):
        return f"{cell.pattern}|{cell.arch}"
"""


def test_sl201_fingerprint_missing_field(tmp_path):
    camp_bad = CAMPAIGN_OK.replace(
        "return repr(sorted(params.__dict__.items()))",
        "return repr(params.seed)")
    diags = lint(tmp_path, {HEAP: SIM_SPECS, CAMPAIGN: camp_bad},
                 only={"SL201"})
    assert rules_of(diags) == {"SL201"}
    assert "'window_bytes'" in diags[0].message
    # covering __dict__ is field-complete by construction
    assert not lint(tmp_path, {CAMPAIGN: CAMPAIGN_OK}, only={"SL201"})


def test_sl202_cell_key_missing_field(tmp_path):
    camp_bad = CAMPAIGN_OK.replace('f"{cell.pattern}|{cell.arch}"',
                                   'f"{cell.pattern}"')
    diags = lint(tmp_path, {HEAP: SIM_SPECS, CAMPAIGN: camp_bad},
                 only={"SL202"})
    assert rules_of(diags) == {"SL202"}
    assert "'arch'" in diags[0].message


def test_sl202_experiment_expansion_counts_as_coverage(tmp_path):
    # cell_key that calls cell.experiment() inherits whatever the
    # expansion reads off self
    camp = CAMPAIGN_OK.replace('f"{cell.pattern}|{cell.arch}"',
                               'repr(cell.experiment())')
    assert not lint(tmp_path, {HEAP: SIM_SPECS, CAMPAIGN: camp},
                    only={"SL202"})


def test_sl203_experiment_spec_field_not_threaded(tmp_path):
    camp_bad = CAMPAIGN_OK.replace(
        "ExperimentSpec(pattern=self.pattern, arch=self.arch)",
        "ExperimentSpec(pattern=self.pattern)")
    diags = lint(tmp_path, {HEAP: SIM_SPECS, CAMPAIGN: camp_bad},
                 only={"SL203"})
    assert rules_of(diags) == {"SL203"}
    assert "'arch'" in diags[0].message
    assert not lint(tmp_path, {CAMPAIGN: CAMPAIGN_OK}, only={"SL203"})


# -- SL3xx: jit/x64 purity -------------------------------------------------


def test_sl301_global_x64_flip(tmp_path):
    bad = """
        import jax
        jax.config.update("jax_enable_x64", True)
    """
    diags = lint(tmp_path, {"src/somewhere.py": bad}, only={"SL301"})
    assert rules_of(diags) == {"SL301"}
    good = """
        from repro.compat import scoped_x64

        def build():
            with scoped_x64():
                return 1
    """
    assert not lint(tmp_path, {"src/somewhere.py": good}, only={"SL301"})


def test_sl302_host_sync_in_jitted_kernel(tmp_path):
    bad = """
        import jax
        import numpy as np

        @jax.jit
        def kernel(x):
            return float(x.sum()) + np.asarray(x)[0]
    """
    diags = lint(tmp_path, {JAX: bad}, only={"SL302"})
    assert rules_of(diags) == {"SL302"}
    assert len(diags) == 2  # float() and np.asarray()
    # the same code outside a jitted def is host code: fine
    good = bad.replace("@jax.jit\n        ", "")
    assert not lint(tmp_path, {JAX: good}, only={"SL302"})


def test_sl302_wrapped_name_counts_as_jitted(tmp_path):
    # x64(jax.vmap(fifo1)) marks fifo1 jitted through the transform
    bad = """
        import jax

        def x64(fn):
            return fn

        def fifo1(a):
            return a.item()

        scan = x64(jax.vmap(fifo1))
    """
    diags = lint(tmp_path, {JAX: bad}, only={"SL302"})
    assert rules_of(diags) == {"SL302"}


def test_sl303_data_dependent_branch(tmp_path):
    bad = """
        import jax

        @jax.jit
        def kernel(x, flag):
            if flag:
                return x
            while x.sum() > 0:
                x = x - 1
            return x
    """
    diags = lint(tmp_path, {JAX: bad}, only={"SL303"})
    assert rules_of(diags) == {"SL303"}
    assert len(diags) == 2  # the if and the while
    # shape/ndim dispatch resolves at trace time: allowed
    good = """
        import jax

        @jax.jit
        def kernel(x, m):
            if x.ndim == 2:
                m = m[:, None]
            if len(x.shape) > 1 and x.shape[0] > 4:
                return x + m
            return x * m
    """
    assert not lint(tmp_path, {JAX: good}, only={"SL303"})


# -- SL4xx: determinism ----------------------------------------------------


def test_sl401_stdlib_random(tmp_path):
    bad = "import random\n"
    diags = lint(tmp_path, {"src/repro/core/x.py": bad}, only={"SL401"})
    assert rules_of(diags) == {"SL401"}
    # outside the determinism scope (engine paths) it is not flagged
    assert not lint(tmp_path, {"src/repro/core/x.py": "x = 1\n",
                               "src/other/x.py": bad}, only={"SL401"})


def test_sl402_unseeded_rng(tmp_path):
    bad = """
        import numpy as np
        rng = np.random.default_rng()
        legacy = np.random.randint(0, 10)
    """
    diags = lint(tmp_path, {"src/repro/core/x.py": bad}, only={"SL402"})
    assert len(diags) == 2
    good = """
        import numpy as np
        rng = np.random.default_rng(1234)
    """
    assert not lint(tmp_path, {"src/repro/core/x.py": good},
                    only={"SL402"})


def test_sl403_wall_clock(tmp_path):
    bad = """
        import time
        t0 = time.time()
        t1 = time.perf_counter()
    """
    diags = lint(tmp_path, {"src/repro/core/x.py": bad}, only={"SL403"})
    assert len(diags) == 2


def test_sl404_set_iteration(tmp_path):
    bad = """
        def f(xs):
            for x in set(xs):
                yield x
            return [y for y in {1, 2, 3}]
    """
    diags = lint(tmp_path, {"src/repro/core/x.py": bad}, only={"SL404"})
    assert len(diags) == 2
    good = """
        def f(xs):
            for x in sorted(set(xs)):
                yield x
    """
    assert not lint(tmp_path, {"src/repro/core/x.py": good},
                    only={"SL404"})


# -- SL5xx: doc/test tolerance drift ---------------------------------------

PARITY_FIX = """
    PARITY_BANDS: dict = {
        "work_sharing.dts.throughput": 0.03,
    }
    FACTOR_BANDS: dict = {
        "overflow.lanes.rejected": (0.3, 3.0),
    }
"""

DOC_FIX = """
    | Cell | Metric | Bound | Band id |
    |---|---|---|---|
    | work sharing | throughput | <= 3% | `band:work_sharing.dts.throughput` |
    | overflow counters | rejected | 0.3-3x | `band:overflow.lanes.rejected` |
"""


def test_sl501_docs_bound_mismatch(tmp_path):
    doc_bad = DOC_FIX.replace("<= 3%", "<= 5%")
    diags = lint(tmp_path, {PARITY: PARITY_FIX, DOC: doc_bad},
                 only={"SL501"})
    assert rules_of(diags) == {"SL501"}
    assert "3%" in diags[0].message
    assert not lint(tmp_path, {DOC: DOC_FIX}, only={"SL501"})


def test_sl501_unknown_band_id(tmp_path):
    doc_bad = DOC_FIX + \
        "| ghost | x | <= 1% | `band:no.such.band` |\n"
    diags = lint(tmp_path, {PARITY: PARITY_FIX, DOC: doc_bad},
                 only={"SL501"})
    assert any("no.such.band" in d.message for d in diags)


def test_sl501_factor_band_mismatch(tmp_path):
    doc_bad = DOC_FIX.replace("0.3-3x", "0.1-9x")
    diags = lint(tmp_path, {PARITY: PARITY_FIX, DOC: doc_bad},
                 only={"SL501"})
    assert rules_of(diags) == {"SL501"}


def test_sl502_undocumented_band(tmp_path):
    parity_more = PARITY_FIX.replace(
        '"work_sharing.dts.throughput": 0.03,',
        '"work_sharing.dts.throughput": 0.03,\n'
        '        "feedback.dts.median_rtt": 0.035,')
    diags = lint(tmp_path, {PARITY: parity_more, DOC: DOC_FIX},
                 only={"SL502"})
    assert rules_of(diags) == {"SL502"}
    assert "feedback.dts.median_rtt" in diags[0].message


def test_sl50x_bands_split_across_docs(tmp_path):
    """The band tables span several docs (engines.md + chaos.md): SL502
    unions the documented ids, and an SL501 mismatch is reported
    against the doc that carries the row."""
    parity_more = PARITY_FIX.replace(
        '"work_sharing.dts.throughput": 0.03,',
        '"work_sharing.dts.throughput": 0.03,\n'
        '        "chaos.broker.summary": 0.05,')
    chaos_doc = """
        | Scenario | Metric | Bound | Band id |
        |---|---|---|---|
        | broker outage | run stretch | <= 5% | `band:chaos.broker.summary` |
    """
    tree = {PARITY: parity_more, DOC: DOC_FIX,
            "docs/chaos.md": chaos_doc}
    assert not lint(tmp_path, tree, only={"SL501", "SL502"})
    tree["docs/chaos.md"] = chaos_doc.replace("<= 5%", "<= 9%")
    diags = lint(tmp_path, tree, only={"SL501"})
    assert rules_of(diags) == {"SL501"}
    assert diags[0].file == "docs/chaos.md"
    # a chaos doc that dropped its row: SL502 unions across the docs
    # and still notices the gap (lint() writes over the earlier file)
    tree["docs/chaos.md"] = "no parity rows here\n"
    diags = lint(tmp_path, tree, only={"SL502"})
    assert any("chaos.broker.summary" in d.message for d in diags)


def test_sl503_parity_suite_not_importing_bands(tmp_path):
    tree = {
        PARITY: PARITY_FIX, DOC: DOC_FIX,
        "tests/test_engine_parity.py": "THR_TOL = {'dts': 0.03}\n",
        "tests/test_multi_tenant.py":
            "from repro.core.parity import band\n",
    }
    diags = lint(tmp_path, tree, only={"SL503"})
    assert rules_of(diags) == {"SL503"}
    assert diags[0].file == "tests/test_engine_parity.py"


# -- suppression semantics -------------------------------------------------


def test_suppression_with_justification(tmp_path):
    src = """
        import time
        t0 = time.time()  # streamlint: disable=SL403 -- telemetry only
    """
    assert not lint(tmp_path, {"src/repro/core/x.py": src},
                    only={"SL403", "SL001", "SL002"})


def test_suppression_standalone_comment_guards_next_code_line(tmp_path):
    src = """
        import time
        # streamlint: disable=SL403 -- wall-clock telemetry, reported
        # alongside results, never fed into them
        t0 = time.time()
    """
    assert not lint(tmp_path, {"src/repro/core/x.py": src},
                    only={"SL403", "SL001", "SL002"})


def test_sl001_unjustified_suppression(tmp_path):
    src = """
        import time
        t0 = time.time()  # streamlint: disable=SL403
    """
    diags = lint(tmp_path, {"src/repro/core/x.py": src},
                 only={"SL403", "SL001"})
    assert rules_of(diags) == {"SL001"}


def test_sl002_unused_suppression(tmp_path):
    src = """
        x = 1  # streamlint: disable=SL403 -- nothing to suppress here
    """
    diags = lint(tmp_path, {"src/repro/core/x.py": src},
                 only={"SL403", "SL002"})
    assert rules_of(diags) == {"SL002"}


def test_suppression_is_rule_specific(tmp_path):
    src = """
        import time
        t0 = time.time()  # streamlint: disable=SL401 -- wrong rule id
    """
    diags = lint(tmp_path, {"src/repro/core/x.py": src}, only={"SL403"})
    assert rules_of(diags) == {"SL403"}


# -- CLI / report surface --------------------------------------------------


def test_cli_json_report_and_exit_codes(tmp_path):
    (tmp_path / "src").mkdir()
    (tmp_path / "src" / "repro").mkdir()
    core = tmp_path / "src" / "repro" / "core"
    core.mkdir()
    (core / "x.py").write_text("import random\n")
    report = tmp_path / "report.json"
    proc = subprocess.run(
        [sys.executable, "-m", "tools.streamlint", "src",
         "--root", str(tmp_path), "--json", str(report)],
        cwd=REPO_ROOT, capture_output=True, text=True)
    assert proc.returncode == 1
    assert "SL401" in proc.stdout
    data = json.loads(report.read_text())
    assert data["counts"]["SL401"] == 1
    assert data["exit_code"] == 1
    assert any(d["rule"] == "SL401" for d in data["diagnostics"])

    (core / "x.py").write_text("x = 1\n")
    proc = subprocess.run(
        [sys.executable, "-m", "tools.streamlint", "src",
         "--root", str(tmp_path)],
        cwd=REPO_ROOT, capture_output=True, text=True)
    assert proc.returncode == 0


def test_syntax_error_is_a_diagnostic_not_a_crash(tmp_path):
    diags = lint(tmp_path, {"src/repro/core/x.py": "def broken(:\n"})
    assert rules_of(diags) == {"SL900"}


# -- live-tree self-check --------------------------------------------------


def test_live_tree_is_clean():
    """The acceptance gate, as a test: the real tree has no unsuppressed
    findings, and every suppression it does carry is justified."""
    analysis = run_analysis(REPO_ROOT, ["src", "benchmarks"])
    assert analysis.failures == [], [d.format() for d in analysis.failures]
    suppressed = [d for d in analysis.diagnostics if d.suppressed]
    assert all(d.justified for d in suppressed)
    # the live tree exercises the suppression machinery (campaign.py's
    # wall-clock telemetry) — keep this test honest about that
    assert suppressed, "expected justified suppressions in campaign.py"


def test_live_docs_table_matches_constants():
    """SL5xx sees the real docs/engines.md and repro.core.parity."""
    analysis = run_analysis(REPO_ROOT, ["src"],
                            only={"SL501", "SL502", "SL503"})
    assert analysis.failures == [], [d.format() for d in analysis.failures]
