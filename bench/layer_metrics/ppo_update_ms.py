"""Wall milliseconds per PPO update call (`ppo_update_batch`), through the
benchmark's wrapper."""
from bench.harness import MissingLayer


def read(record):
    if record["drive"] != "train":
        return None
    if not record["ppo_s"]:
        raise MissingLayer("training ran no timed PPO update")
    return sum(record["ppo_s"]) / len(record["ppo_s"]) * 1e3
