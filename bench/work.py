"""Operations and bytes of one batched policy call, from its shapes.

The policy call (`act_batch`) runs the tree-CNN actor over B plan trees
and samples or takes the argmax of the masked logits. Only the work the
algorithm needs is counted: the matmuls over each lane's real nodes (not
the padding a program may add), and the bytes that must cross HBM at
least once - the actor's parameters, the real part of the inputs, and
the outputs. Elementwise work (bias, leaky ReLU, mask, max-pool, softmax)
is left out: it is a few per cent of the matmul operations and runs on
other units.
"""
from __future__ import annotations

from typing import Iterable, Mapping

F32 = 4


def tree_cnn_flops(nodes: int, feat: int, hidden: int) -> int:
    """Multiply-adds x 2 of three tree convolutions over `nodes` nodes:
    each convolution multiplies the node, its left and its right child by
    a (d_in, hidden) matrix."""
    per_node = 3 * 2 * feat * hidden + 2 * (3 * 2 * hidden * hidden)
    return nodes * per_node


def head_flops(hidden: int, head_hidden: int, actions: int) -> int:
    return 2 * hidden * head_hidden + 2 * head_hidden * actions


def policy_call_work(real_nodes: Iterable[int], batch: int, *, feat: int,
                     hidden: int, head_hidden: int, actions: int,
                     param_bytes: int) -> Mapping[str, int]:
    """FLOPs and bytes of one `act_batch` call.

    `real_nodes` lists the node count of each lane that holds a state;
    `batch` is the call's batch size (outputs are written for every lane).
    """
    real_nodes = list(real_nodes)
    flops = sum(tree_cnn_flops(n, feat, hidden) for n in real_nodes)
    flops += len(real_nodes) * head_flops(hidden, head_hidden, actions)
    # inputs: feat (n, F) f32, left/right int32, mask f32, per real lane;
    # the action mask and the 2-word PRNG key per lane
    in_bytes = sum(n * (feat + 3) * F32 for n in real_nodes)
    in_bytes += len(real_nodes) * (actions * F32 + 2 * F32)
    # outputs: action int32, logp f32, advanced key (2 x uint32) per lane
    out_bytes = batch * 4 * F32
    return {"flops": int(flops), "bytes": int(param_bytes + in_bytes
                                               + out_bytes)}


def roofline_seconds(work: Mapping[str, int], peak: Mapping[str, float]):
    """(least seconds, bound) - the larger of operations over peak FLOP/s
    and bytes over peak bytes/s, and which of the two it is."""
    t_flops = work["flops"] / peak["flops_per_s"]
    t_bytes = work["bytes"] / peak["bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
