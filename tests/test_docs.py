"""Documentation integrity: every artifact DESIGN.md's per-experiment index
references must exist; the required deliverable files are present."""
import pathlib
import re

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_deliverable_files_exist():
    for p in ("README.md", "DESIGN.md", "EXPERIMENTS.md", "pyproject.toml",
              "src/repro/launch/mesh.py", "src/repro/launch/dryrun.py",
              "bench/run.py", "examples/quickstart.py"):
        assert (ROOT / p).exists(), p


def test_design_experiment_index_targets_exist():
    text = (ROOT / "DESIGN.md").read_text()
    refs = re.findall(r"`(bench/[\w/.]+?\.(?:py|json))`", text)
    assert refs, "DESIGN.md must index benchmark modules"
    for r in set(refs):
        assert (ROOT / r).exists(), f"DESIGN.md references missing {r}"


def test_arch_configs_cover_assignment():
    from repro.configs import registry
    assert len(registry.ARCHS) == 10
    cells = registry.assigned_cells()
    assert len(cells) == 40
    runnable = [c for c in cells if c[2]]
    skipped = [c for c in cells if not c[2]]
    assert len(skipped) == 8              # long_500k on quadratic archs
    for arch, shape, ok, why in skipped:
        assert shape == "long_500k" and "quadratic" in why


def test_dryrun_sets_xla_flags_first():
    src = (ROOT / "src/repro/launch/dryrun.py").read_text().splitlines()
    assert src[0].startswith("import os")
    assert "xla_force_host_platform_device_count=512" in src[1]


def test_no_global_device_count_override():
    """Only the dry-run drivers may force 512 devices (tests/benches must
    see 1 device)."""
    allowed = {"dryrun.py", "perf_climb.py", "test_docs.py"}
    for p in ROOT.rglob("*.py"):
        if p.name in allowed:
            continue
        if ".tmp" in str(p):
            continue
        txt = p.read_text()
        assert "xla_force_host_platform_device_count" not in txt, p
