"""Mean milliseconds a batched policy call spends in `lqrs.policy.feed`:
the node trim, the host-to-device copies and the dispatch of the policy
program. The spans' time in the window over the number of `lqrs.policy`
spans (calls) there."""
from bench import trace_reduce


def read(record):
    return trace_reduce.self_ms_per(record, ("lqrs.policy.feed",),
                                    per="lqrs.policy")
