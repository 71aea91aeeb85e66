"""The policy call's share of its roofline: the least time the chip could
take for a call's work (the larger of FLOPs over peak FLOP/s and bytes
over peak bytes/s, `bench/work.py`), averaged over the window's calls,
over the mean device time of the policy program's runs in the trace. The peaks are the chip's bf16 ones;
the policy runs its matmuls at full float32."""
from bench import trace_reduce


def read(record):
    if record["drive"] != "serve":
        return None
    work, dims, peak = record["work"], record["dims"], record["peak"]
    runs, seconds = trace_reduce.policy_runs(record)
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for c in record["policy_calls"]:
        nodes = c["inputs"][3].sum(axis=1)
        w = work.policy_call_work([int(n) for n in nodes[c["real"]]],
                                  len(nodes), **dims)
        t, bound = work.roofline_seconds(w, peak)
        least += t
        bounds[bound] += 1
    record["notes"].append(f"policy_roofline_pct: {bounds['memory']} calls "
                           f"memory-bound, {bounds['compute']} "
                           f"compute-bound")
    # per call: the least time over the device time of a traced run
    return (least / len(record["policy_calls"])) / (seconds / runs) * 100.0
