"""Repo hygiene: fast checks that keep generated artifacts out of git.

Bytecode (`__pycache__/*.pyc`), hypothesis example databases and JAX
compilation caches are generated at run time; this gate keeps that class
of file from being committed."""

import subprocess
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def _tracked_files():
    out = subprocess.run(
        ["git", "ls-files"], cwd=REPO, capture_output=True, text=True,
        check=True,
    )
    return out.stdout.splitlines()


def test_no_bytecode_tracked():
    """No compiled-python, test-cache or compile-cache artifacts may be
    tracked by git."""
    generated = {"__pycache__", ".pytest_cache", ".hypothesis", ".jax_cache"}
    bad = [
        f for f in _tracked_files()
        if f.endswith(".pyc") or generated & set(f.split("/"))
    ]
    assert bad == [], f"generated artifacts tracked by git: {bad}"


def test_gitignore_covers_bytecode():
    """The root .gitignore must keep covering the artifact classes."""
    patterns = (REPO / ".gitignore").read_text().splitlines()
    for needed in ("__pycache__/", "*.pyc", ".pytest_cache/", ".hypothesis/",
                   ".jax_cache/"):
        assert needed in patterns, f".gitignore is missing {needed!r}"


def test_every_registered_kernel_dispatches_through_ops():
    """Every kernel in the registry must have a public wrapper in
    kernels/ops.py that resolves its arm through `registry.resolve` — a
    spec with no dispatching wrapper is dead tuning surface, and a wrapper
    outside the registry re-creates the hard-coded-path problem."""
    import sys

    sys.path.insert(0, str(REPO / "src"))
    try:
        from repro.kernels import ops as K
        from repro.kernels.registry import REGISTRY
    finally:
        sys.path.pop(0)
    ops_src = (REPO / "src" / "repro" / "kernels" / "ops.py").read_text()
    missing = [
        name for name in REGISTRY
        if not callable(getattr(K, name, None)) or name not in ops_src
    ]
    assert missing == [], (
        f"registered kernels with no registry-dispatched wrapper in "
        f"kernels/ops.py: {missing}"
    )
    assert "REG.resolve(" in ops_src or "registry.resolve(" in ops_src


def test_no_interpret_literals_outside_kernels():
    """Backend dispatch is the registry's job: no tracked .py file outside
    src/repro/kernels/ may pass an ``interpret=`` kwarg (the pre-registry
    hard-coded ``interpret=not _on_tpu()`` pattern)."""
    import re

    pat = re.compile(r"\binterpret\s*=")
    offenders = []
    for f in _tracked_files():
        if not f.endswith(".py") or f.startswith("src/repro/kernels/"):
            continue
        if f == "tests/test_hygiene.py":  # this gate's own docstring
            continue
        for i, line in enumerate((REPO / f).read_text().splitlines(), 1):
            if pat.search(line):
                offenders.append(f"{f}:{i}")
    assert offenders == [], (
        f"interpret= literals outside the kernel package: {offenders}"
    )


def test_every_stats_field_reaches_the_registry():
    """Every accounting field — each `SchedulerStats` dataclass field and
    each `SmartPQStats` NamedTuple field — must surface in the engine's
    metrics registry after a `health()` sync (prefixes ``sched_`` /
    ``pq_``).  A stats field that never reaches `repro.obs` is a second
    accounting surface, which is exactly what the unified-telemetry PR
    removed; this gate keeps it removed."""
    import dataclasses
    import sys

    sys.path.insert(0, str(REPO / "src"))
    try:
        from repro.core.smartpq import SmartPQStats
        from repro.serve.engine import EngineConfig, ServeEngine
        from repro.serve.scheduler import SchedulerStats

        eng = ServeEngine(None, None, EngineConfig(batch_size=2))
        eng.health()  # syncs every stats surface into the registry
        gauges = eng.obs.metrics.to_dict()["gauges"]
        missing = []
        for f in dataclasses.fields(SchedulerStats):
            prefix = f"sched_{f.name}"
            if not any(k.startswith(prefix) for k in gauges):
                missing.append(f"SchedulerStats.{f.name}")
        for name in SmartPQStats._fields:
            prefix = f"pq_{name}"
            if not any(k.startswith(prefix) for k in gauges):
                missing.append(f"SmartPQStats.{name}")
    finally:
        sys.path.pop(0)
    assert missing == [], (
        f"stats fields never mirrored into the metrics registry: {missing}"
    )


def test_every_fault_injector_is_exercised():
    """Every injector registered in `repro.faults.INJECTORS` must appear by
    name in tests/test_faults.py — a registry entry with no chaos test is a
    fault path nobody has ever watched fail."""
    import sys

    sys.path.insert(0, str(REPO / "src"))
    try:
        from repro.faults import INJECTORS
    finally:
        sys.path.pop(0)
    chaos_src = (REPO / "tests" / "test_faults.py").read_text()
    missing = [name for name in INJECTORS if name not in chaos_src]
    assert missing == [], (
        f"fault injectors with no test coverage in test_faults.py: "
        f"{missing}"
    )
