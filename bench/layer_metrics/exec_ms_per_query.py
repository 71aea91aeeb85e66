"""Executor self milliseconds per completed query: the self time of the
program's `lqrs.exec.scan` and `lqrs.exec.join` spans (base-table scans
and join stages, stage-cache hits among them, with their charges) in the
window, over the queries completed."""
from bench import trace_reduce


def read(record):
    return trace_reduce.self_ms_per(record, ("lqrs.exec.scan",
                                             "lqrs.exec.join"))
