"""The 95th percentile, over every decision in the window, of the wall time
of the batched policy call that made it: a call counts once per lane it
decided. It is what a stage boundary waits for on the chip path."""
import numpy as np


def read(record):
    calls = record["policy_calls"]
    if record["drive"] != "serve" or not calls:
        return None
    ms = np.repeat([c["s"] * 1e3 for c in calls], [c["lanes"] for c in calls])
    return float(np.percentile(ms, 95))
