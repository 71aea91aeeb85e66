"""Helpers the benchmark's CPU tests share: the harness's run function
(`bench/run.py` past its look for a chip) and cells cut to a tiny scale."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

SCALE = 0.05


def _run_module():
    import importlib.util
    spec = importlib.util.spec_from_file_location("bench_run_main",
                                                  ROOT / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


RUN = _run_module()


def tiny(plan):
    plan["config"]["scale"] = SCALE
    plan["config"]["setup_training"]["episodes"] = 8
    if plan["traffic"]["drive"] == "train":
        plan["traffic"]["episodes_per_call"] = 8
    return plan


def plan_for(config, traffic, name="test.cell"):
    """A cell built from the files directly, as BENCHMARK.json would."""
    spec = harness.load_spec()
    cell = {"name": name, "config": config, "traffic": traffic, "chips": 1}
    spec = dict(spec, workloads=spec["workloads"] + [cell])
    if not any(c["name"] == config for c in spec["configs"]):
        spec["configs"] = spec["configs"] + [
            {"name": config, "file": f"bench/configs/{config}.json"}]
    plan = tiny(harness.cell_plan(spec, name))
    # every end-to-end metric: each reader returns None off its own drive
    plan["end_to_end"] = [m for m in spec["end_to_end"]
                          if m["name"] != "train_episodes_per_s"] + [
        {"name": "train_episodes_per_s", "unit": "episodes/s"}]
    return plan
