"""The policy call's operation and byte count against a hand count, and the
roofline arithmetic."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import work  # noqa: E402


def test_policy_call_work_hand_count():
    # one lane of 2 real nodes, features 3, hidden 2, head 2, 4 actions:
    # conv1 3 matmuls (3->2) = 3*2*3*2 = 36, conv2 and conv3 3*2*2*2 = 24
    # each, per node 84, for 2 nodes 168; head 2*2*2 + 2*2*4 = 24.
    # bytes: parameters 100; inputs 2 nodes * (3 features + left, right,
    # mask) * 4 = 48, action mask 4*4 = 16, key 8; outputs 2 lanes * 16.
    w = work.policy_call_work([2], 2, feat=3, hidden=2, head_hidden=2,
                              actions=4, param_bytes=100)
    assert w == {"flops": 192, "bytes": 100 + 48 + 16 + 8 + 32}


def test_padding_lanes_cost_only_outputs():
    one = work.policy_call_work([5], 1, feat=26, hidden=96, head_hidden=96,
                                actions=172, param_bytes=0)
    padded = work.policy_call_work([5], 8, feat=26, hidden=96,
                                   head_hidden=96, actions=172,
                                   param_bytes=0)
    assert padded["flops"] == one["flops"]
    assert padded["bytes"] - one["bytes"] == 7 * 16


def test_roofline_picks_the_larger_bound():
    peak = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    assert work.roofline_seconds({"flops": 200, "bytes": 10}, peak) == \
        (2.0, "compute")
    assert work.roofline_seconds({"flops": 100, "bytes": 50}, peak) == \
        (5.0, "memory")
