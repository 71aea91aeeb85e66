"""Decision-model encoders: TreeCNN (default) + LSTM / FCNN / tree-
transformer ("QueryFormer-lite") for the paper's Tab. III / Fig. 11(b)
ablation. All share one interface:

  init_encoder(key, kind, feat_dim, hidden) -> params
  apply_encoder(params, kind, feat, left, right, mask) -> (hidden,) pooled

and are pure-JAX, jit/vmap friendly (fixed MAX_NODES padding).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import normal_init, split_keys

# The TPU's default f32 matmul is one bf16 pass: its logits differ from the
# host's by ~1e-2, enough to flip a greedy action or a gate verdict. Every
# policy matmul, and the fused kernel's, runs at full f32 on every backend.
MATMUL_PRECISION = "highest"


# ------------------------------------------------------------------ treecnn
def _init_treeconv(key, d_in, d_out):
    ks = split_keys(key, 4)
    s = 1.0 / (3 * d_in) ** 0.5
    return {"wr": normal_init(ks[0], (d_in, d_out), jnp.float32, s),
            "wl": normal_init(ks[1], (d_in, d_out), jnp.float32, s),
            "wrt": normal_init(ks[2], (d_in, d_out), jnp.float32, s),
            "b": jnp.zeros((d_out,), jnp.float32)}


def _apply_treeconv(p, h, left, right, mask):
    """Neo-style binary tree convolution: combine each node with its
    children (null child = slot 0, kept zero)."""
    hl = h[left]
    hr = h[right]
    out = h @ p["wr"] + hl @ p["wl"] + hr @ p["wrt"] + p["b"]
    out = jax.nn.leaky_relu(out)
    return out * mask[:, None]          # re-zero padding (incl. slot 0)


def _init_treecnn(key, feat_dim, hidden):
    ks = split_keys(key, 3)
    return {"conv1": _init_treeconv(ks[0], feat_dim, hidden),
            "conv2": _init_treeconv(ks[1], hidden, hidden),
            "conv3": _init_treeconv(ks[2], hidden, hidden)}


def _apply_treecnn(p, feat, left, right, mask):
    h = _apply_treeconv(p["conv1"], feat * mask[:, None], left, right, mask)
    h = _apply_treeconv(p["conv2"], h, left, right, mask)
    h = _apply_treeconv(p["conv3"], h, left, right, mask) + h
    # dynamic max-pool over real nodes
    neg = jnp.where(mask[:, None] > 0, h, -jnp.inf)
    pooled = jnp.max(neg, axis=0)
    return jnp.where(jnp.isfinite(pooled), pooled, 0.0)


# ------------------------------------------------------------------ lstm
def _init_lstm(key, feat_dim, hidden):
    ks = split_keys(key, 2)
    s = 1.0 / (feat_dim + hidden) ** 0.5
    return {"wx": normal_init(ks[0], (feat_dim, 4 * hidden), jnp.float32, s),
            "wh": normal_init(ks[1], (hidden, 4 * hidden), jnp.float32, s),
            "b": jnp.zeros((4 * hidden,), jnp.float32)}


def _apply_lstm(p, feat, left, right, mask):
    """Pre-order node sequence (the padded order IS pre-order) -> last state."""
    H = p["wh"].shape[0]

    def step(carry, xm):
        h, c = carry
        x, m = xm
        z = x @ p["wx"] + h @ p["wh"] + p["b"]
        i, f, g, o = jnp.split(z, 4)
        c_new = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
        h = m * h_new + (1 - m) * h
        c = m * c_new + (1 - m) * c
        return (h, c), None

    (h, _), _ = jax.lax.scan(step, (jnp.zeros(H), jnp.zeros(H)),
                             (feat, mask))
    return h


# ------------------------------------------------------------------ fcnn
def _init_fcnn(key, feat_dim, hidden, max_nodes):
    ks = split_keys(key, 2)
    d = feat_dim * max_nodes
    return {"w1": normal_init(ks[0], (d, hidden), jnp.float32, d ** -0.5),
            "b1": jnp.zeros((hidden,), jnp.float32),
            "w2": normal_init(ks[1], (hidden, hidden), jnp.float32, hidden ** -0.5),
            "b2": jnp.zeros((hidden,), jnp.float32)}


def _apply_fcnn(p, feat, left, right, mask):
    x = (feat * mask[:, None]).reshape(-1)
    h = jax.nn.leaky_relu(x @ p["w1"] + p["b1"])
    return jax.nn.leaky_relu(h @ p["w2"] + p["b2"])


# ------------------------------------------------------- queryformer-lite
def _init_qf(key, feat_dim, hidden, n_heads=4, n_layers=2):
    ks = split_keys(key, 2 + 4 * n_layers)
    p = {"inp": normal_init(ks[0], (feat_dim, hidden), jnp.float32, feat_dim ** -0.5),
         "layers": []}
    for i in range(n_layers):
        base = 2 + 4 * i
        p["layers"].append({
            "wq": normal_init(ks[base], (hidden, hidden), jnp.float32, hidden ** -0.5),
            "wk": normal_init(ks[base + 1], (hidden, hidden), jnp.float32, hidden ** -0.5),
            "wv": normal_init(ks[base + 2], (hidden, hidden), jnp.float32, hidden ** -0.5),
            "wo": normal_init(ks[base + 3], (hidden, hidden), jnp.float32, hidden ** -0.5),
        })
    return p


def _apply_qf(p, feat, left, right, mask):
    """Self-attention over node tokens with a tree-structure bias: children
    attend to parents (adjacency bias), QueryFormer-style but miniature."""
    h = (feat * mask[:, None]) @ p["inp"]
    N = h.shape[0]
    adj = jnp.zeros((N, N), jnp.float32)
    idx = jnp.arange(N)
    adj = adj.at[idx, left].set(1.0).at[idx, right].set(1.0)
    adj = adj + adj.T + jnp.eye(N)
    bias = jnp.where(adj > 0, 0.0, -4.0)          # soft structural prior
    key_mask = jnp.where(mask > 0, 0.0, -1e9)
    for lp in p["layers"]:
        q, k, v = h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]
        s = q @ k.T / (h.shape[-1] ** 0.5) + bias + key_mask[None, :]
        a = jax.nn.softmax(s, axis=-1)
        h = h + (a @ v) @ lp["wo"]
        h = h * mask[:, None]
    neg = jnp.where(mask[:, None] > 0, h, -jnp.inf)
    pooled = jnp.max(neg, axis=0)
    return jnp.where(jnp.isfinite(pooled), pooled, 0.0)


# ------------------------------------------------------------------ public
def init_encoder(key, kind, feat_dim, hidden, max_nodes=64):
    if kind == "treecnn":
        return _init_treecnn(key, feat_dim, hidden)
    if kind == "lstm":
        return _init_lstm(key, feat_dim, hidden)
    if kind == "fcnn":
        return _init_fcnn(key, feat_dim, hidden, max_nodes)
    if kind == "queryformer":
        return _init_qf(key, feat_dim, hidden)
    raise ValueError(kind)


def apply_encoder(params, kind, feat, left, right, mask, *, fused=False,
                  interpret=None):
    """Single state (N, F) -> (hidden,), or a batch (B, N, F) -> (B, hidden).

    Batched treecnn may lower to the fused VMEM-resident Pallas kernel
    (`fused=True`) — one kernel for all three conv layers + residual +
    masked max-pool, building child one-hots in-kernel. The fused kernel
    carries a custom VJP (backward rematerializes through the jnp
    reference), so it serves training losses as well as rollout inference.
    """
    fn = {"treecnn": _apply_treecnn, "lstm": _apply_lstm,
          "fcnn": _apply_fcnn, "queryformer": _apply_qf}[kind]
    if getattr(feat, "ndim", 2) == 3:          # batched states
        if fused and kind == "treecnn":
            from repro.kernels.tree_conv import tree_cnn_fused
            return tree_cnn_fused(feat, left, right, mask, params,
                                  interpret=interpret)
        fn = jax.vmap(fn, in_axes=(None, 0, 0, 0, 0))
    with jax.default_matmul_precision(MATMUL_PRECISION):
        return fn(params, feat, left, right, mask)


def init_mlp_head(key, d_in, d_hidden, d_out):
    ks = split_keys(key, 2)
    return {"w1": normal_init(ks[0], (d_in, d_hidden), jnp.float32, d_in ** -0.5),
            "b1": jnp.zeros((d_hidden,), jnp.float32),
            "w2": normal_init(ks[1], (d_hidden, d_out), jnp.float32, d_hidden ** -0.5),
            "b2": jnp.zeros((d_out,), jnp.float32)}


def apply_mlp_head(p, x):
    with jax.default_matmul_precision(MATMUL_PRECISION):
        h = jax.nn.leaky_relu(x @ p["w1"] + p["b1"])
        return h @ p["w2"] + p["b2"]
