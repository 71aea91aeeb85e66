"""FFN layers: gated-MLP and GShard-style capacity-factor MoE.

The MoE dispatch avoids the classic (tokens, E, C) one-hot dispatch einsum
(memory hog at 1M tokens); instead tokens are *scattered* into an
(E, C, d_model) buffer using cumsum-derived positions-in-expert, expert
matmuls run as a single batched einsum (MXU-friendly), and results are
gathered back and combined with router weights. With experts sharded over the
'model' mesh axis this lowers to the standard expert-parallel all-to-all
pattern under GSPMD.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import act_fn, apply_dense, init_dense, normal_init, split_keys
from repro.sharding import act as act_sharding


# ------------------------------------------------------------------ dense MLP
def init_mlp(key, cfg, d_ff=None):
    ks = split_keys(key, 3)
    D, F = cfg.d_model, (d_ff or cfg.d_ff)
    p = {}
    p.update(init_dense(ks[0], D, F, cfg.pdtype, name="w_gate"))
    p.update(init_dense(ks[1], D, F, cfg.pdtype, name="w_up"))
    p.update(init_dense(ks[2], F, D, cfg.pdtype, name="w_down"))
    return p


def apply_mlp(p, x, cfg):
    act = act_fn(cfg.act)
    g = act(apply_dense(p, x, "w_gate", cfg.cdtype))
    u = apply_dense(p, x, "w_up", cfg.cdtype)
    return apply_dense(p, g * u, "w_down", cfg.cdtype)


# ------------------------------------------------------------------ MoE
def init_moe(key, cfg):
    m = cfg.moe
    ks = split_keys(key, 5)
    D, F, E = cfg.d_model, cfg.moe_d_ff, m.n_experts
    p = {
        "router": normal_init(ks[0], (D, E), jnp.float32, stddev=0.02),
        "moe_wg": normal_init(ks[1], (E, D, F), cfg.pdtype),
        "moe_wu": normal_init(ks[2], (E, D, F), cfg.pdtype),
        "moe_wd": normal_init(ks[3], (E, F, D), cfg.pdtype),
    }
    if m.shared_expert_ff:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=m.shared_expert_ff)
    return p


def apply_moe(p, x, cfg):
    """x: (B, S, D). Returns (y, aux_metrics dict of scalar losses)."""
    m = cfg.moe
    B, S, D = x.shape
    E, K = m.n_experts, m.top_k
    T = B * S
    xt = x.reshape(T, D)
    act = act_fn(cfg.act)

    logits = (xt.astype(jnp.float32) @ p["router"])            # (T, E) fp32
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, K)                       # (T, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    pol = act_sharding.current()
    if (pol is not None and pol.moe_dispatch == "shard_map"
            and pol.mesh is not None and S > 1):
        y = _dispatch_shard_map(xt, eidx, gate, p, cfg, pol, act)
        aux = _aux_losses(m, logits, probs, eidx)
        y = y.reshape(B, S, D)
        if m.shared_expert_ff:
            y = y + apply_mlp(p["shared"], x, cfg)
        return y, aux
    local = (pol is not None and pol.moe_dispatch == "local"
             and S > 1 and (T * K) % 32 == 0)
    flat_e = eidx.reshape(-1)                                  # (T*K,) token-major
    xk = jnp.repeat(xt, K, axis=0).astype(cfg.cdtype)          # (T*K, D)
    xk = act_sharding.constrain(xk, {0: "dp"})

    if local:
        # ---- block-local dispatch (the §Perf collective fix) -------------
        # The global-cumsum scatter below writes dp-sharded tokens into
        # GLOBAL capacity slots of the (E, C, D) buffer; GSPMD cannot prove
        # the writes disjoint across data shards and lowers it as partial
        # buffers + a giant all-reduce (measured 2.4 TB/device on dbrx).
        # Giving every token block its OWN capacity slice makes the scatter
        # shard-local; the block axis stays dp-sharded, experts tp-sharded,
        # and cross-shard movement becomes the (cheap) buf resharding.
        NB = 32                                # >= dp x pod; divides T*K
        Tb = (T * K) // NB
        Cb = max(int(m.capacity_factor * Tb / E), 1) if S > 1 else Tb
        eb = flat_e.reshape(NB, Tb)
        onehot = jax.nn.one_hot(eb, E, dtype=jnp.int32)        # (NB, Tb, E)
        pos = jnp.cumsum(onehot, axis=1) - 1                   # block-local
        pos_t = jnp.take_along_axis(pos, eb[..., None], axis=2)[..., 0]
        xb = xk.reshape(NB, Tb, D)
        buf = jnp.zeros((NB, E, Cb, D), cfg.cdtype)
        bidx = jnp.broadcast_to(jnp.arange(NB)[:, None], (NB, Tb))
        buf = buf.at[bidx, eb, pos_t].set(xb, mode="drop")
        buf = act_sharding.constrain(buf, {0: "dp", 1: "tp"})
        g = jnp.einsum("becd,edf->becf", buf, p["moe_wg"].astype(cfg.cdtype))
        u = jnp.einsum("becd,edf->becf", buf, p["moe_wu"].astype(cfg.cdtype))
        h = act(g) * u
        yb = jnp.einsum("becf,efd->becd", h, p["moe_wd"].astype(cfg.cdtype))
        keep = (pos_t < Cb).astype(cfg.cdtype)
        ytk = (yb[bidx, eb, jnp.minimum(pos_t, Cb - 1)]
               * keep[..., None]).reshape(T * K, D)
    else:
        # ---- paper-era global dispatch (kept as the measured baseline) ---
        # decode (S==1): no-drop — a dropped token at serving time corrupts
        # the stream; capacity waste is negligible at T = B tokens.
        C = (T * K) if S == 1 else (int(m.capacity_factor * T * K / E) or 1)
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)    # (T*K, E)
        pos = jnp.cumsum(onehot, axis=0) - 1                   # global slots
        pos_t = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
        buf = jnp.zeros((E, C, D), cfg.cdtype)
        buf = buf.at[flat_e, pos_t].set(xk, mode="drop")
        buf = act_sharding.constrain(buf, {0: "tp"})
        g = jnp.einsum("ecd,edf->ecf", buf, p["moe_wg"].astype(cfg.cdtype))
        u = jnp.einsum("ecd,edf->ecf", buf, p["moe_wu"].astype(cfg.cdtype))
        h = act(g) * u
        yb = jnp.einsum("ecf,efd->ecd", h, p["moe_wd"].astype(cfg.cdtype))
        keep = (pos_t < C).astype(cfg.cdtype)                  # dropped -> 0
        ytk = yb[flat_e, jnp.minimum(pos_t, C - 1)] * keep[:, None]

    y = (ytk.reshape(T, K, D) * gate.astype(cfg.cdtype)[..., None]).sum(axis=1)

    aux = _aux_losses(m, logits, probs, eidx)
    y = y.reshape(B, S, D)
    if m.shared_expert_ff:
        y = y + apply_mlp(p["shared"], x, cfg)
    return y, aux


def _aux_losses(m, logits, probs, eidx):
    """GShard load-balance + router z-loss."""
    E = m.n_experts
    me = probs.mean(axis=0)                                    # (E,)
    frac = jax.nn.one_hot(eidx[:, 0], E, dtype=jnp.float32).mean(axis=0)
    return {
        "moe_aux": m.aux_loss * E * jnp.sum(me * frac),
        "moe_z": m.router_z_loss * jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
    }


def _dispatch_shard_map(xt, eidx, gate, p, cfg, pol, act):
    """Explicit per-shard MoE dispatch (the §Perf dbrx fix).

    Key observation: activations are dp-sharded but REPLICATED over the
    model axis, so each model shard can select its own experts' tokens
    locally — the dispatch needs NO communication at all. Each shard builds
    a (E_local, C_local, D) buffer from its replicated token slice, runs
    its experts, scatters results back to token positions (zeros for
    foreign tokens) and a single psum over the model axis combines the
    top-k partial outputs. Wire cost: one (T_local, D) all-reduce per
    layer — ~50x less than the partial-buffer all-reduce GSPMD emits for
    the global scatter (measured 2.4 TB/device on dbrx train_4k).
    """
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    m = cfg.moe
    E, K = m.n_experts, m.top_k
    D = cfg.d_model
    El = E // pol.tp_size
    dp = pol.dp_axes if len(pol.dp_axes) > 1 else pol.dp_axes[0]
    tp = pol.tp_axis
    cdt = cfg.cdtype

    def body(xt_l, e_l, g_l, wg_l, wu_l, wd_l):
        Tl = xt_l.shape[0]
        Cl = max(int(m.capacity_factor * Tl * K / E), 1)
        e0 = jax.lax.axis_index(tp).astype(jnp.int32) * El
        fe = e_l.reshape(-1) - e0                     # local expert index
        mine = (fe >= 0) & (fe < El)
        fe_c = jnp.clip(fe, 0, El - 1)
        onehot = jax.nn.one_hot(fe_c, El, dtype=jnp.int32) * mine[:, None]
        pos = jnp.cumsum(onehot, axis=0) - 1
        pos_t = jnp.take_along_axis(pos, fe_c[:, None], 1)[:, 0]
        keep = mine & (pos_t < Cl)
        xk = jnp.repeat(xt_l, K, axis=0)
        buf = jnp.zeros((El, Cl, D), cdt)
        # out-of-range expert index => dropped by scatter mode="drop"
        tgt_e = jnp.where(keep, fe_c, El)
        buf = buf.at[tgt_e, jnp.where(keep, pos_t, 0)].set(xk, mode="drop")
        g = jnp.einsum("ecd,edf->ecf", buf, wg_l)
        u = jnp.einsum("ecd,edf->ecf", buf, wu_l)
        yb = jnp.einsum("ecf,efd->ecd", act(g) * u, wd_l)
        ytk = (yb[fe_c, jnp.minimum(pos_t, Cl - 1)]
               * keep[:, None].astype(cdt))
        y_l = (ytk.reshape(Tl, K, D)
               * g_l[..., None].astype(cdt)).sum(axis=1)
        return jax.lax.psum(y_l, tp)                  # combine top-k partials

    fn = shard_map(
        body, mesh=pol.mesh,
        in_specs=(P(dp, None), P(dp, None), P(dp, None),
                  P(tp, None, None), P(tp, None, None), P(tp, None, None)),
        out_specs=P(dp, None))
    return fn(xt.astype(cdt), eidx, gate,
              p["moe_wg"].astype(cdt), p["moe_wu"].astype(cdt),
              p["moe_wd"].astype(cdt))
