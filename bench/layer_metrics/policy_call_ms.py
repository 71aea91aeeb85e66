"""Mean wall milliseconds of a batched policy call (`act_batch`), through
the benchmark's wrapper: encoding excepted, it is what a stage boundary
waits for on the chip path."""


def read(record):
    calls = record["policy_calls"]
    if record["drive"] != "serve" or not calls:
        return None
    return sum(c["s"] for c in calls) / len(calls) * 1e3
