"""Share of the executor's stages that the stage cache served: the
program's `lqrs.exec.scan` and `lqrs.exec.join` spans in the window whose
`hit` count is 1, over all of them (a stage that failed carries no count
and is a miss)."""
from bench import trace_reduce
from bench.harness import MissingLayer

SPANS = ("lqrs.exec.scan", "lqrs.exec.join")


def read(record):
    if record["drive"] != "serve" or not record.get("comps"):
        return None
    _, counts = trace_reduce.program_spans(record, SPANS)
    if not any("hit" in counts[n] for n in SPANS):
        raise MissingLayer("no executor span in the window carries a hit "
                           "count")
    hits = sum(counts[n].get("hit", 0) for n in SPANS)
    return hits / sum(counts[n]["spans"] for n in SPANS) * 100.0
