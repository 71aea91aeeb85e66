"""Mean lanes decided per scheduler tick (`LaneScheduler.decide_sizes`)."""
import numpy as np


def read(record):
    sizes = record.get("decide_sizes")
    if record["drive"] != "serve" or not sizes:
        return None
    return float(np.mean(sizes))
