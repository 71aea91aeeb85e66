"""`chip_smoke.py` off the chip: its phases at a tiny scale on the CPU, its
refusal to report success without a TPU, and the compile-cache helper."""
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro import jax_cache  # noqa: E402


def test_phases_at_tiny_scale(tmp_path):
    tr = chip_smoke.phase_train(scale=0.05, episodes=8)
    assert tr["n_params"] == 161549 and tr["max_param_move"] > 0
    sv = chip_smoke.phase_serve(tr["db"], tr["wl"], tr["est"], tr["agent"],
                                tmp_path, scale=0.05)
    assert sv["n_completed"] == sv["n_arrivals"] == 3 * len(tr["wl"].test)
    assert sv["n_checked"] + sv["n_unreferenced"] + sv["n_failed"] == \
        sv["n_arrivals"]
    assert sv["learn"]["updates"] > 0
    assert [p.name for p in tmp_path.iterdir()] == ["policy_store"]
    kn = chip_smoke.phase_kernel(tr["agent"], sv["comps"])
    assert kn["actions_equal_fused"] and kn["actions_equal_cpu"]
    assert not kn["mosaic_kernel"]       # off the TPU Pallas interprets


def test_row_check_rejects_a_wrong_cardinality():
    def comp(rows, failed=False):
        stage = types.SimpleNamespace(out_rows=rows)
        return types.SimpleNamespace(
            query=types.SimpleNamespace(name="q1"),
            result=types.SimpleNamespace(failed=failed, stages=[stage]))

    assert chip_smoke.check_rows([comp(7), comp(0, failed=True)],
                                 {"q1": 7}) == (1, 0)
    with pytest.raises(chip_smoke.SmokeFailure, match="q1: 8 rows"):
        chip_smoke.check_rows([comp(7), comp(8)], {"q1": 7})
    with pytest.raises(chip_smoke.SmokeFailure, match="no completion"):
        chip_smoke.check_rows([comp(7)], {"q1": None})


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_refuses_without_a_tpu(tmp_path, where):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    script, cwd = ROOT / "chip_smoke.py", ROOT
    if where == "alone":             # no other file of the repository
        env.pop("PYTHONPATH", None)
        cwd = tmp_path / "lone"
        cwd.mkdir()
        script = Path(shutil.copy(script, cwd))
    r = subprocess.run([sys.executable, str(script), "--out",
                        str(tmp_path / "out")], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert not (tmp_path / "out").exists()


@pytest.mark.parametrize("env_dir", ["/elsewhere/cache", None])
def test_enable_compile_cache(monkeypatch, env_dir):
    from jax.experimental.compilation_cache import compilation_cache
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    before = {n: getattr(jax.config, n) for n in names}
    try:
        path = jax_cache.enable_compile_cache()
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        if env_dir is None:
            assert path == jax.config.jax_compilation_cache_dir == \
                str(ROOT / ".jax_cache")
        else:   # JAX read the variable at import; the helper sets nothing
            assert path == env_dir
            assert jax.config.jax_compilation_cache_dir == \
                before["jax_compilation_cache_dir"]
    finally:
        for n, v in before.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()
