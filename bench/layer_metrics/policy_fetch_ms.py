"""Mean milliseconds a batched policy call spends in `lqrs.policy.fetch`:
the wait for the policy program and the copy of its outputs back to the
host. The spans' time in the window over the number of `lqrs.policy`
spans (calls) there."""
from bench import trace_reduce


def read(record):
    return trace_reduce.self_ms_per(record, ("lqrs.policy.fetch",),
                                    per="lqrs.policy")
