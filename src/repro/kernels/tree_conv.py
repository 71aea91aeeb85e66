"""Neo-style tree convolution as a Pallas TPU kernel (AQORA's decision-
model hot spot: called at every stage boundary of every running query).

TPU adaptation: child gathers (h[left], h[right]) are data-dependent loads
— poison for the TPU's vector memory. We re-express them as one-hot
matmuls: gather(h, idx) == onehot(idx) @ h, turning the whole layer into
three MXU matmuls fused in one VMEM-resident kernel:

    out = leaky_relu(h @ Wr + (L @ h) @ Wl + (R @ h) @ Wrt + b) * mask

Trees are padded to MAX_NODES=64, so a whole batch tile (trees x nodes x
feat) fits VMEM comfortably; grid is over tree batches.

Two entry points:

  tree_conv      — ONE conv layer; builds the (B, N, N) one-hots on the
                   host with jax.nn.one_hot and ships them through HBM
                   (legacy; kept as the per-layer building block).
  tree_cnn_fused — the WHOLE encoder: all three conv layers + residual +
                   masked max-pool in one VMEM-resident kernel over
                   multi-tree tiles. Child one-hot matrices are built
                   in-kernel from `iota == idx` comparisons, so no
                   O(B*N^2) one-hot traffic ever touches HBM and no
                   intermediate (B, N, H) activations round-trip either.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.nets import MATMUL_PRECISION


def _kernel(h_ref, lo_ref, ro_ref, m_ref, wr_ref, wl_ref, wrt_ref, b_ref,
            o_ref):
    h = h_ref[0].astype(jnp.float32)          # (N, F)
    m = m_ref[0].astype(jnp.float32)          # (N, 1)
    h = h * m
    lo = lo_ref[0].astype(jnp.float32)        # (N, N) one-hot(left)
    ro = ro_ref[0].astype(jnp.float32)
    hl = jax.lax.dot_general(lo, h, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    hr = jax.lax.dot_general(ro, h, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    out = (h @ wr_ref[...].astype(jnp.float32)
           + hl @ wl_ref[...].astype(jnp.float32)
           + hr @ wrt_ref[...].astype(jnp.float32)
           + b_ref[...].astype(jnp.float32)[None, :])
    out = jnp.where(out > 0, out, 0.01 * out)           # leaky_relu
    o_ref[0] = (out * m).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def tree_conv(feat, left, right, mask, wr, wl, wrt, b, *, interpret=False):
    """feat: (B, N, F); left/right: (B, N) int32 child indices (0 = null,
    row 0 must be a zero row); mask: (B, N); weights (F, H), b (H,).
    Returns (B, N, H)."""
    Bt, N, F = feat.shape
    H = wr.shape[1]
    onehot_l = jax.nn.one_hot(left, N, dtype=feat.dtype)     # (B, N, N)
    onehot_r = jax.nn.one_hot(right, N, dtype=feat.dtype)
    m = mask[..., None].astype(feat.dtype)

    return pl.pallas_call(
        _kernel,
        grid=(Bt,),
        in_specs=[
            pl.BlockSpec((1, N, F), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, N, N), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, N, N), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, N, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((F, H), lambda i: (0, 0)),
            pl.BlockSpec((F, H), lambda i: (0, 0)),
            pl.BlockSpec((F, H), lambda i: (0, 0)),
            pl.BlockSpec((H,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((1, N, H), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Bt, N, H), feat.dtype),
        interpret=interpret,
    )(feat, onehot_l, onehot_r, m, wr, wl, wrt, b)


# ------------------------------------------------------------- fused encoder
def _fused_kernel(h_ref, li_ref, ri_ref, m_ref,
                  w1r, w1l, w1t, b1, w2r, w2l, w2t, b2, w3r, w3l, w3t, b3,
                  o_ref):
    """One multi-tree tile: (TB, N, F) feats -> (TB, H) pooled encodings.

    The child one-hots are rebuilt in VMEM from index comparisons — row n
    of L is one-hot at column left[n], so L @ h == h[left] — and every
    intermediate activation lives and dies in VMEM.
    """
    TB = h_ref.shape[0]
    N = h_ref.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (N, N), 1)

    def layer(h, m, lo, ro, wr, wl, wt, b):
        hl = jax.lax.dot_general(lo, h, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        hr = jax.lax.dot_general(ro, h, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        out = (h @ wr[...].astype(jnp.float32)
               + hl @ wl[...].astype(jnp.float32)
               + hr @ wt[...].astype(jnp.float32)
               + b[...].astype(jnp.float32)[None, :])
        out = jnp.where(out > 0, out, 0.01 * out)           # leaky_relu
        return out * m

    def one_tree(t, carry):
        m = m_ref[t].astype(jnp.float32)                    # (N, 1)
        lo = (iota == li_ref[t]).astype(jnp.float32)        # (N, N) in VMEM
        ro = (iota == ri_ref[t]).astype(jnp.float32)
        h = h_ref[t].astype(jnp.float32) * m                # (N, F)
        h1 = layer(h, m, lo, ro, w1r, w1l, w1t, b1)
        h2 = layer(h1, m, lo, ro, w2r, w2l, w2t, b2)
        h3 = layer(h2, m, lo, ro, w3r, w3l, w3t, b3) + h2   # residual
        neg = jnp.where(m > 0, h3, -jnp.inf)                # masked max-pool
        pooled = jnp.max(neg, axis=0)
        pooled = jnp.where(jnp.isfinite(pooled), pooled, 0.0)
        o_ref[t] = pooled.astype(o_ref.dtype)
        return carry

    with jax.default_matmul_precision(MATMUL_PRECISION):
        jax.lax.fori_loop(0, TB, one_tree, 0)


def _fused_forward(feat, left, right, mask, params, tile, interpret):
    """Forward pallas_call for the fused encoder (no autodiff rules)."""
    B, N, F = feat.shape
    H = params["conv1"]["wr"].shape[1]
    TB = min(tile, B)
    Bp = ((B + TB - 1) // TB) * TB
    if Bp != B:                       # pad to a whole number of tiles; the
        pad = ((0, Bp - B), (0, 0))   # all-zero mask rows pool to 0
        feat = jnp.pad(feat, pad + ((0, 0),))
        left = jnp.pad(left, pad)
        right = jnp.pad(right, pad)
        mask = jnp.pad(mask, pad)
    li = left.astype(jnp.int32)[..., None]                  # (Bp, N, 1)
    ri = right.astype(jnp.int32)[..., None]
    m = mask[..., None].astype(feat.dtype)                  # (Bp, N, 1)

    wspec = lambda shape: pl.BlockSpec(shape, lambda i: tuple(0 for _ in shape))
    w = []
    specs = []
    for lname in ("conv1", "conv2", "conv3"):
        p = params[lname]
        w += [p["wr"], p["wl"], p["wrt"], p["b"]]
        d_in = p["wr"].shape[0]
        specs += [wspec((d_in, H)), wspec((d_in, H)), wspec((d_in, H)),
                  wspec((H,))]

    out = pl.pallas_call(
        _fused_kernel,
        grid=(Bp // TB,),
        in_specs=[
            pl.BlockSpec((TB, N, F), lambda i: (i, 0, 0)),
            pl.BlockSpec((TB, N, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((TB, N, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((TB, N, 1), lambda i: (i, 0, 0)),
        ] + specs,
        out_specs=pl.BlockSpec((TB, H), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, H), feat.dtype),
        interpret=interpret,
    )(feat, li, ri, m, *w)
    return out[:B]


# ------------------------------------------------- custom VJP for training
def _ref_tree_cnn(feat, left, right, mask, params):
    """jnp reference of the fused kernel for ONE tree — the SAME math
    (one-hot gather == h[idx] for in-range indices, leaky_relu slope 0.01,
    residual, masked max-pool), used to build the backward pass."""
    m = mask[:, None]
    h = feat * m

    def layer(h, p):
        out = (h @ p["wr"] + h[left] @ p["wl"] + h[right] @ p["wrt"]
               + p["b"])
        out = jnp.where(out > 0, out, 0.01 * out)
        return out * m

    h1 = layer(h, params["conv1"])
    h2 = layer(h1, params["conv2"])
    h3 = layer(h2, params["conv3"]) + h2
    neg = jnp.where(m > 0, h3, -jnp.inf)
    pooled = jnp.max(neg, axis=0)
    return jnp.where(jnp.isfinite(pooled), pooled, 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _fused_with_vjp(feat, left, right, mask, params, tile, interpret):
    return _fused_forward(feat, left, right, mask, params, tile, interpret)


def _fused_fwd(feat, left, right, mask, params, tile, interpret):
    out = _fused_forward(feat, left, right, mask, params, tile, interpret)
    return out, (feat, left, right, mask, params)


def _fused_bwd(tile, interpret, residuals, g):
    """Backward by rematerialization: re-run the (cheap, (B,N,H)-sized)
    jnp reference forward and pull the cotangent through it. The fused
    kernel keeps its VMEM-resident forward on the hot path; the backward
    trades one extra reference forward for not spilling any intermediate
    activations to HBM during inference."""
    feat, left, right, mask, params = residuals

    def ref(f, m, p):
        return jax.vmap(_ref_tree_cnn, in_axes=(0, 0, 0, 0, None))(
            f, left, right, m, p)

    with jax.default_matmul_precision(MATMUL_PRECISION):
        _, pullback = jax.vjp(ref, feat, mask, params)
        gf, gm, gp = pullback(g)
    zero_int = lambda x: np.zeros(x.shape, jax.dtypes.float0)
    return gf, zero_int(left), zero_int(right), gm, gp


_fused_with_vjp.defvjp(_fused_fwd, _fused_bwd)


def tree_cnn_fused(feat, left, right, mask, params, *, tile=8,
                   interpret=None):
    """Fused TreeCNN encoder: conv1..conv3 + residual + masked max-pool.

    feat: (B, N, F); left/right: (B, N) int32 child indices (0 = null,
    row 0 must be a zero row); mask: (B, N); params: the core.nets treecnn
    dict {"conv1"|"conv2"|"conv3": {"wr","wl","wrt","b"}}. Returns (B, H)
    pooled encodings. Only (B, N) index vectors cross HBM — the one-hot
    matrices and all intermediate activations exist in VMEM only.
    `interpret=None` auto-selects interpreter mode off-TPU. It is resolved
    here, outside the jit, so the choice is part of the compiled program's
    cache key and a Mosaic trace is never reused for an interpreted call.

    Differentiable w.r.t. feat, mask and params via a custom VJP (backward
    rematerializes through the jnp reference), so PPO training can run
    the fused kernel — not just rollout inference.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _fused_jit(feat, left, right, mask, params, tile, interpret)


_fused_jit = jax.jit(_fused_with_vjp, static_argnums=(5, 6))
