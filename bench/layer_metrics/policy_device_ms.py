"""Device milliseconds per batched policy call: the trace's runs of the
policy program that started inside the benchmark's policy-call spans."""
from bench import trace_reduce


def read(record):
    if record["drive"] != "serve":
        return None
    runs, seconds = trace_reduce.policy_runs(record)
    return seconds / runs * 1e3
