"""Wall milliseconds per completed query spent in the learning hooks'
completion callbacks (harvest, PPO update on the clone, gate and swap)."""
from bench.harness import MissingLayer


def read(record):
    if not record.get("online"):
        return None
    if not record["learn_s"]:
        raise MissingLayer("online learning ran no timed callback")
    return sum(record["learn_s"]) / len(record["comps"]) * 1e3
