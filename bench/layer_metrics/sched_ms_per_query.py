"""The scheduler's own milliseconds per completed query: the self time of
the program's `lqrs.serve`, `lqrs.tick`, `lqrs.admit`, `lqrs.decide`,
`lqrs.apply`, `lqrs.resume` and `lqrs.finish` spans in the window (what
the scheduler does outside the executor, the encoding and the policy
call: planning, admission, applying actions, finishing trajectories),
over the queries completed. Where learning runs, `lqrs.finish` holds its
callbacks, so this reads the scheduler only without it."""
from bench import trace_reduce


def read(record):
    return trace_reduce.self_ms_per(record, (
        "lqrs.serve", "lqrs.tick", "lqrs.admit", "lqrs.decide",
        "lqrs.apply", "lqrs.resume", "lqrs.finish"))
