"""Documentation integrity: links resolve, the README stays a
quickstart, and docs/ never drifts from the code it describes.

All checks are grep-driven over the file tree — no `repro` imports, so
the fast tier never touches jax-marked modules and the CI `docs` job
can run with pytest alone.  The symbol check is the `solo_terms`-style
drift guard: every ``module.symbol`` / ``Class.member`` reference in
docs/*.md (and README.md) must still exist in the named file, and
every call-looking bare reference must still appear somewhere under
src/ or benchmarks/.
"""
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
DOC_FILES = sorted((REPO / "docs").glob("*.md")) + [REPO / "README.md"]

README_MAX_LINES = 120

# module-level references: `provisioner.alloc_gpus` etc.
MODULES = {
    "perf_model": "src/repro/core/perf_model.py",
    "perf_model_vec": "src/repro/core/perf_model_vec.py",
    "perf_model_jax": "src/repro/core/perf_model_jax.py",
    "physics_jax": "src/repro/serving/physics_jax.py",
    "provisioner": "src/repro/core/provisioner.py",
    "queueing": "src/repro/core/queueing.py",
    "replication": "src/repro/core/replication.py",
    "coefficients": "src/repro/core/coefficients.py",
    "baselines": "src/repro/core/baselines.py",
    "experiments": "src/repro/core/experiments.py",
    "types": "src/repro/core/types.py",
    "simulator": "src/repro/serving/simulator.py",
    "physics": "src/repro/serving/physics.py",
    "traces": "src/repro/serving/traces.py",
    "faults": "src/repro/serving/faults.py",
    "controller": "src/repro/serving/controller.py",
    "workload": "src/repro/serving/workload.py",
    "telemetry": "src/repro/serving/telemetry.py",
    "trace": "src/repro/core/trace.py",
    "telemetry_report": "benchmarks/telemetry_report.py",
}

# class-level references: `VecCluster.alloc_all`, `SimResult.stats`, ...
CLASSES = {
    "WorkloadCoefficients": "src/repro/core/types.py",
    "HardwareSpec": "src/repro/core/types.py",
    "WorkloadSpec": "src/repro/core/types.py",
    "Placement": "src/repro/core/types.py",
    "ProvisioningPlan": "src/repro/core/types.py",
    "PlannerConfig": "src/repro/core/types.py",
    "ProbeCache": "src/repro/core/provisioner.py",
    "InfeasibleError": "src/repro/core/provisioner.py",
    "DeviceCapError": "src/repro/core/provisioner.py",
    "PlanState": "src/repro/core/provisioner.py",
    "CoeffArrays": "src/repro/core/perf_model_vec.py",
    "VecCluster": "src/repro/core/perf_model_vec.py",
    "BudgetModel": "src/repro/core/queueing.py",
    "QueueingDelay": "src/repro/core/queueing.py",
    "SimResult": "src/repro/serving/simulator.py",
    "ServedInstance": "src/repro/serving/simulator.py",
    "SimTestbed": "src/repro/serving/simulator.py",
    "Trace": "src/repro/serving/traces.py",
    "ArrivalEstimator": "src/repro/serving/controller.py",
    "ControllerConfig": "src/repro/serving/controller.py",
    "Reconciler": "src/repro/serving/controller.py",
    "Controller": "src/repro/serving/controller.py",
    "PlanEdit": "src/repro/serving/controller.py",
    "Telemetry": "src/repro/serving/telemetry.py",
    "RingBuffer": "src/repro/serving/telemetry.py",
    "ControlEvent": "src/repro/serving/telemetry.py",
}

_LINK = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
_TICK = re.compile(r"`([^`]+)`")
_DOTTED = re.compile(r"^([A-Za-z_][A-Za-z0-9_]*)\.([A-Za-z_][A-Za-z0-9_]*)")
_CALL = re.compile(r"^([A-Za-z_][A-Za-z0-9_]*)\(")
_PATHISH = re.compile(r"^[\w./-]+\.(py|md|json|yml|ini|txt)$")


def _defines(source: str, name: str) -> bool:
    """`name` is defined in `source` as a function, class, assignment,
    dataclass field, or method (grep-level check, no imports)."""
    return re.search(
        rf"(?m)^\s*(def\s+{name}\b|class\s+{name}\b|{name}\s*[=:])",
        source) is not None


@pytest.fixture(scope="module")
def all_source() -> str:
    chunks = []
    for root in ("src", "benchmarks"):
        for p in sorted((REPO / root).rglob("*.py")):
            chunks.append(p.read_text())
    return "\n".join(chunks)


@pytest.mark.parametrize("doc", DOC_FILES, ids=lambda p: p.name)
def test_intra_repo_links_resolve(doc):
    """Every non-http markdown link points at an existing file."""
    missing = []
    for target in _LINK.findall(doc.read_text()):
        if target.startswith(("http://", "https://", "mailto:")):
            continue
        path = target.split("#", 1)[0]
        if not path:
            continue               # pure in-page anchor
        if not (doc.parent / path).exists() and not (REPO / path).exists():
            missing.append(target)
    assert not missing, f"{doc.name}: broken links {missing}"


def test_readme_stays_a_quickstart():
    """The deep dives live in docs/; the README is a <= 120-line
    quickstart (CI enforces the same bound)."""
    n = len((REPO / "README.md").read_text().splitlines())
    assert n <= README_MAX_LINES, \
        f"README.md has {n} lines > {README_MAX_LINES}; move content to docs/"


def test_docs_reference_only_existing_paths():
    """Backticked path-looking tokens must exist — either repo-relative
    (tests/..., benchmarks/...) or in the `core/x.py` / `serving/x.py`
    shorthand the docs use for src/repro modules."""
    missing = []
    for doc in DOC_FILES:
        for tok in _TICK.findall(doc.read_text()):
            if _PATHISH.match(tok) and "/" in tok:
                if not ((REPO / tok).exists()
                        or (REPO / "src" / "repro" / tok).exists()):
                    missing.append(f"{doc.name}: {tok}")
    assert not missing, f"docs reference nonexistent files: {missing}"


def test_docs_symbols_exist(all_source):
    """Every `module.symbol` / `Class.member` reference resolves against
    the named file, and every call-looking bare reference appears
    somewhere in the source tree — the docs-drift guard."""
    stale = []
    for doc in DOC_FILES:
        for tok in _TICK.findall(doc.read_text()):
            m = _DOTTED.match(tok)
            if m:
                owner, name = m.groups()
                path = MODULES.get(owner) or CLASSES.get(owner)
                if path is None:
                    continue       # not a tracked namespace (e.g. np.*)
                if not _defines((REPO / path).read_text(), name):
                    stale.append(f"{doc.name}: `{tok}` — no {name} in {path}")
                continue
            m = _CALL.match(tok)
            if m and not re.search(rf"\b{m.group(1)}\b", all_source):
                stale.append(f"{doc.name}: `{tok}` not found in source")
    assert not stale, "stale doc references:\n" + "\n".join(stale)


def test_module_map_is_current():
    """The maps above must themselves not rot."""
    for rel in list(MODULES.values()) + list(CLASSES.values()):
        assert (REPO / rel).exists(), f"tracked file missing: {rel}"
    for cls, rel in CLASSES.items():
        assert re.search(rf"(?m)^class\s+{cls}\b",
                         (REPO / rel).read_text()), \
            f"class {cls} not defined in {rel}"
