"""Milliseconds per lane decision spent encoding the lane's state and
action mask: the time of the program's `lqrs.encode` spans (one a lane
decided) over their number."""
from bench import trace_reduce


def read(record):
    return trace_reduce.self_ms_per(record, ("lqrs.encode",),
                                    per="lqrs.encode")
