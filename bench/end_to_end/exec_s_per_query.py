"""Modelled Spark execution seconds per completed query: `RunResult.latency`
(a failure charged as the cluster model charges it), summed over the
window's completed queries and divided by their count."""


def read(record):
    comps = record.get("comps")
    if record["drive"] != "serve" or not comps:
        return None
    return sum(c.result.latency for c in comps) / len(comps)
