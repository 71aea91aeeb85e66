"""Host time per completed query outside the policy calls and the learning
callbacks: window wall time minus the benchmark's spans around both, over
the queries completed. It is the executor's stages, encoding, action
masks and plan rewrites, and the scheduler's own loop."""


def read(record):
    comps = record.get("comps")
    if record["drive"] != "serve" or not comps:
        return None
    rest = (record["window_s"] - sum(c["s"] for c in record["policy_calls"])
            - sum(record["learn_s"]))
    return rest / len(comps) * 1e3
