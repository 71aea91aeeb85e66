"""AQORA agent: TreeCNN actor + critic, masked policy, PPO update (Alg. 1).

Actor and critic are separate encoder+head networks (~150k parameters
combined at the defaults, matching Tab. III). All state tensors are padded
to MAX_NODES, trajectories to (max_steps+1) states, so the PPO update jits
once per workload.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import nets
from repro.core.actions import ActionSpace
from repro.core.encoding import MAX_NODES, WorkloadMeta
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.spans import span


@dataclasses.dataclass(frozen=True)
class AgentConfig:
    net: str = "treecnn"               # treecnn | lstm | fcnn | queryformer
    hidden: int = 96
    head_hidden: int = 96
    families: Tuple[str, ...] = ("cbo", "lead", "noop")
    max_steps: int = 3                 # hook interventions per query (§VI-A)
    ppo_epochs: int = 6
    clip: float = 0.2
    entropy: float = 0.02              # η
    gamma: float = 1.0                 # Alg. 1 sets γ=1
    lr_actor: float = 3e-4
    lr_critic: float = 1e-3
    curriculum: Tuple[float, float] = (0.25, 0.55)
    failure_penalty: float = 300.0     # R(τ) -= sqrt(300) on failure
    fused_treecnn: bool = False        # VMEM-resident fused kernel on the
                                       #   batched inference AND training
                                       #   paths (custom VJP; TPU)


def _node_bucket(n_used: int) -> int:
    """Smallest multiple of 16 covering the deepest used node slot.

    A plan tree over n relations has at most 2n-1 nodes (+ the null slot),
    and encode_state numbers them contiguously from 1, so every state of a
    workload fits in one trimmed node dimension — ONE compiled shape per
    batch size instead of always paying the full MAX_NODES padding."""
    b = 16
    while b < n_used:
        b += 16
    return min(b, MAX_NODES)


class AqoraAgent:
    def __init__(self, meta: WorkloadMeta, cfg: AgentConfig = AgentConfig(),
                 seed: int = 0):
        self.meta = meta
        self.cfg = cfg
        self.space = ActionSpace(meta.n_tables_max, cfg.families)
        k = jax.random.split(jax.random.PRNGKey(seed), 5)
        F, H = meta.feat_dim, cfg.hidden
        self.actor = {
            "enc": nets.init_encoder(k[0], cfg.net, F, H, MAX_NODES),
            "head": nets.init_mlp_head(k[1], H, cfg.head_hidden, self.space.d)}
        self.critic = {
            "enc": nets.init_encoder(k[2], cfg.net, F, H, MAX_NODES),
            "head": nets.init_mlp_head(k[3], H, cfg.head_hidden, 1)}
        self.aopt = adamw_init(self.actor)
        self.copt = adamw_init(self.critic)
        self._acfg = AdamWConfig(lr=cfg.lr_actor, weight_decay=0.0, grad_clip=5.0)
        self._ccfg = AdamWConfig(lr=cfg.lr_critic, weight_decay=0.0, grad_clip=5.0)
        self.rng = jax.random.PRNGKey(seed + 1)
        # static per-workload trimmed node dim (fcnn flattens MAX_NODES)
        self._nodes = MAX_NODES if cfg.net == "fcnn" \
            else _node_bucket(2 * meta.n_tables_max)
        self._build_jits()

    # ------------------------------------------------------------- nets
    def _build_jits(self):
        net = self.cfg.net
        fused = self.cfg.fused_treecnn

        def logits_fn(actor, feat, left, right, mask):
            h = nets.apply_encoder(actor["enc"], net, feat, left, right, mask)
            return nets.apply_mlp_head(actor["head"], h)

        def value_fn(critic, feat, left, right, mask):
            h = nets.apply_encoder(critic["enc"], net, feat, left, right, mask)
            return nets.apply_mlp_head(critic["head"], h)[0]

        def logits_fn_b(actor, feat, left, right, mask):
            # batched (B, N, F) encoder; may lower to the fused Pallas
            # TreeCNN (differentiable — it carries a custom VJP)
            h = nets.apply_encoder(actor["enc"], net, feat, left, right, mask,
                                   fused=fused)
            return nets.apply_mlp_head(actor["head"], h)

        def value_fn_b(critic, feat, left, right, mask):
            h = nets.apply_encoder(critic["enc"], net, feat, left, right, mask,
                                   fused=fused)
            return nets.apply_mlp_head(critic["head"], h)[:, 0]

        self._logits = jax.jit(logits_fn)
        self._value = jax.jit(value_fn)
        self._logits_b = jax.jit(logits_fn_b)
        self._value_b = jax.jit(value_fn_b)

        def act_batch_fn(actor, feat, left, right, mask, amask, keys, explore):
            """One forward + masked categorical sample for B lanes. Each
            lane's PRNG chain advances in-kernel (split -> sample), so the
            host only carries the returned key bytes — no per-lane device
            round trips."""
            lg = logits_fn_b(actor, feat, left, right, mask)
            lg = jnp.where(amask > 0, lg, -1e9)
            logp_all = jax.nn.log_softmax(lg, axis=-1)
            pairs = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
            new_keys, subs = pairs[:, 0], pairs[:, 1]
            if explore:
                a = jax.vmap(jax.random.categorical)(subs, lg)
            else:
                a = jnp.argmax(lg, axis=-1)
            a = a.astype(jnp.int32)
            logp = jnp.take_along_axis(logp_all, a[:, None], 1)[:, 0]
            return a, logp, new_keys

        self._act_batch_jit = jax.jit(act_batch_fn,
                                      static_argnames=("explore",))

        clip, eta = self.cfg.clip, self.cfg.entropy

        def masked_logp(actor, feat, left, right, mask, amask):
            # fused agents train through the fused kernel's custom VJP;
            # the vmapped path is kept as the (numerically identical)
            # default
            if fused:
                lg = logits_fn_b(actor, feat, left, right, mask)
            else:
                lg = jax.vmap(logits_fn, (None, 0, 0, 0, 0))(
                    actor, feat, left, right, mask)
            lg = jnp.where(amask > 0, lg, -1e9)
            return jax.nn.log_softmax(lg, axis=-1)

        def actor_loss(actor, batch):
            logp_all = masked_logp(actor, batch["feat"], batch["left"],
                                   batch["right"], batch["mask"], batch["amask"])
            logp = jnp.take_along_axis(logp_all, batch["action"][:, None], 1)[:, 0]
            ratio = jnp.exp(logp - batch["old_logp"])
            q = batch["q"]
            un = ratio * q
            cl = jnp.clip(ratio, 1 - clip, 1 + clip) * q
            l_clip = -jnp.sum(jnp.minimum(un, cl) * batch["valid"]) / \
                jnp.maximum(batch["valid"].sum(), 1.0)
            p = jnp.exp(logp_all)
            ent_term = jnp.sum(jnp.where(batch["amask"] > 0, p * logp_all, 0.0), -1)
            l_ent = jnp.sum(ent_term * batch["valid"]) / \
                jnp.maximum(batch["valid"].sum(), 1.0)
            return l_clip + eta * l_ent

        def critic_loss(critic, sbatch):
            if fused:
                v = value_fn_b(critic, sbatch["feat"], sbatch["left"],
                               sbatch["right"], sbatch["mask"])
            else:
                v = jax.vmap(value_fn, (None, 0, 0, 0, 0))(
                    critic, sbatch["feat"], sbatch["left"], sbatch["right"],
                    sbatch["mask"])
            err = (v - sbatch["v_target"]) ** 2
            return jnp.sum(err * sbatch["valid"]) / jnp.maximum(sbatch["valid"].sum(), 1.0)

        def update(actor, critic, aopt, copt, batch, sbatch):
            al, agrad = jax.value_and_grad(actor_loss)(actor, batch)
            cl_, cgrad = jax.value_and_grad(critic_loss)(critic, sbatch)
            actor, aopt, _ = adamw_update(actor, agrad, aopt, self._acfg)
            critic, copt, _ = adamw_update(critic, cgrad, copt, self._ccfg)
            return actor, critic, aopt, copt, al, cl_

        epochs = self.cfg.ppo_epochs

        def update_epochs(actor, critic, aopt, copt, batch, sbatch):
            """All e PPO epochs in ONE jitted call (lax.fori_loop), so an
            episode-batch costs a single dispatch; params + optimizer
            state are donated and rewritten in place."""
            def body(_, carry):
                actor, critic, aopt, copt, _, _ = carry
                return update(actor, critic, aopt, copt, batch, sbatch)
            init = (actor, critic, aopt, copt,
                    jnp.float32(0.0), jnp.float32(0.0))
            return jax.lax.fori_loop(0, epochs, body, init)

        self._update_epochs = jax.jit(update_epochs, donate_argnums=(0, 1, 2, 3))

    # ------------------------------------------------------------- policy
    def policy_probs(self, enc_state, amask: np.ndarray) -> np.ndarray:
        feat, left, right, mask = enc_state
        lg = self._logits(self.actor, feat, left, right, mask)
        lg = jnp.where(jnp.asarray(amask) > 0, lg, -1e9)
        return np.asarray(jax.nn.softmax(lg))

    def act(self, enc_state, amask: np.ndarray, explore: bool = True) -> Tuple[int, float]:
        probs = self.policy_probs(enc_state, amask)
        if explore:
            self.rng, k = jax.random.split(self.rng)
            a = int(jax.random.choice(k, len(probs), p=jnp.asarray(probs)))
        else:
            a = int(np.argmax(probs))
        return a, float(np.log(max(probs[a], 1e-12)))

    def act_batch(self, feat, left, right, mask, amask, keys,
                  explore: bool = True):
        """Act for B lanes in one jitted forward + masked categorical sample.

        feat (B, N, F), left/right (B, N) int32, mask (B, N), amask (B, d),
        keys (B, 2) uint32 per-lane PRNG keys. Returns numpy
        (actions (B,), logps (B,), advanced keys (B, 2)) with exactly ONE
        device sync — the single device_get below.

        The node dimension is trimmed to the workload's static bucket
        before the forward: trailing padding rows never influence real
        nodes, so this is exact, and it cuts the dominant O(N) encoder
        cost without fragmenting the jit cache.
        """
        # profile spans: the call as a whole, the host side that feeds the
        # program (trim, copies to the device, dispatch) and the wait for
        # its results; the program itself runs as `jit_act_batch_fn`
        with span("lqrs.policy") as sp:
            with span("lqrs.policy.feed"):
                n = np.shape(feat)[1]
                if self.cfg.net != "fcnn":   # fcnn flattens all MAX_NODES
                    mask = np.asarray(mask)
                    n = min(self._nodes,
                            _node_bucket(int(mask.sum(axis=1).max()) + 1))
                    feat, left, right, mask = (np.asarray(feat)[:, :n],
                                               np.asarray(left)[:, :n],
                                               np.asarray(right)[:, :n],
                                               mask[:, :n])
                sp.set_metadata(nodes=n)
                a, logp, new_keys = self._act_batch_jit(
                    self.actor, jnp.asarray(feat), jnp.asarray(left),
                    jnp.asarray(right), jnp.asarray(mask),
                    jnp.asarray(amask), jnp.asarray(keys), explore=explore)
            with span("lqrs.policy.fetch"):
                a, logp, new_keys = jax.device_get((a, logp, new_keys))
        return np.asarray(a), np.asarray(logp), np.asarray(new_keys)

    def act_keyed(self, enc_state, amask: np.ndarray, key,
                  explore: bool = True) -> Tuple[int, float, np.ndarray]:
        """Serial act with an explicit PRNG key chain — one lane of
        act_batch, so seeded serial and batched rollouts sample
        identically. Returns (action, logp, advanced key)."""
        feat, left, right, mask = enc_state
        a, logp, new_keys = self.act_batch(
            feat[None], left[None], right[None], mask[None],
            np.asarray(amask)[None], np.asarray(key, np.uint32)[None],
            explore=explore)
        return int(a[0]), float(logp[0]), new_keys[0]

    def value(self, enc_state) -> float:
        feat, left, right, mask = enc_state
        return float(self._value(self.critic, feat, left, right, mask))

    # ------------------------------------------------------------- update
    def ppo_update(self, traj) -> Dict[str, float]:
        """Single-trajectory PPO update — an episode-batch of one (Alg. 1
        semantics are preserved exactly at batch_size=1)."""
        return self.ppo_update_batch([traj])

    def ppo_update_batch(self, trajs) -> Dict[str, float]:
        """One jitted PPO update over an episode-batch of trajectories.

        Implements Alg. 1 per lane: v_pi from realized returns, q from the
        CURRENT critic (one batched forward over all B*K padded states),
        then e epochs of clipped updates against frozen old probabilities —
        amortizing the jit dispatch and (via donate_argnums) reusing the
        param/optimizer buffers across the whole batch.
        """
        cfg = self.cfg
        trajs = [t for t in trajs if len(t.actions) > 0]
        if not trajs:
            return {"actor_loss": 0.0, "critic_loss": 0.0}
        B = len(trajs)
        K = cfg.max_steps + 1
        F = self.meta.feat_dim

        feat = np.zeros((B, K, MAX_NODES, F), np.float32)
        left = np.zeros((B, K, MAX_NODES), np.int32)
        right = np.zeros((B, K, MAX_NODES), np.int32)
        mask = np.zeros((B, K, MAX_NODES), np.float32)
        svalid = np.zeros((B, K), np.float32)
        v_pi = np.zeros((B, K), np.float32)
        amask = np.zeros((B, K - 1, self.space.d), np.float32)
        action = np.zeros((B, K - 1), np.int32)
        old_logp = np.zeros((B, K - 1), np.float32)
        tvalid = np.zeros((B, K - 1), np.float32)
        ks, n_states_b, rs_b, term_b = [], [], [], []
        for bi, traj in enumerate(trajs):
            k = len(traj.actions)
            n_states = min(len(traj.states), K)
            for i, s in enumerate(traj.states[:K]):
                feat[bi, i], left[bi, i], right[bi, i], mask[bi, i] = s
            svalid[bi, :n_states] = 1.0
            # v_pi(s_i) = sum_{j>i} r_j - sqrt(T_execute)  (Alg. 1 line 2;
            # the paper's +sqrt is a sign typo — R(tau) subtracts it)
            rs = np.asarray(traj.rewards, np.float32)
            term = -np.sqrt(traj.t_execute)
            for i in range(n_states):
                v_pi[bi, i] = rs[i:].sum() + term
            for t in range(k):
                amask[bi, t] = traj.masks[t]
                action[bi, t] = traj.actions[t]
                old_logp[bi, t] = traj.logps[t]
                tvalid[bi, t] = 1.0
            ks.append(k)
            n_states_b.append(n_states)
            rs_b.append(rs)
            term_b.append(term)

        # trim the node dimension to the batch's bucketed max (exact:
        # trailing padding never influences real nodes; fcnn excepted).
        # Buckets are multiples of 16, so the jit cache sees at most
        # MAX_NODES/16 shapes per batch size.
        N = MAX_NODES
        if cfg.net != "fcnn":
            N = min(self._nodes,
                    _node_bucket(int(mask.sum(axis=2).max()) + 1))
            feat, left = feat[:, :, :N], left[:, :, :N]
            right, mask = right[:, :, :N], mask[:, :, :N]

        # q_t = r_{t+1} + v_phi(s_{t+1}) - v_phi(s_t) for every ACTION
        # (Alg. 1's trailing 0 belongs to the terminal state s_k, which has
        # no action). If the terminal state s_k was not encodable, fall back
        # to its realized value v_pi(s_k) = -sqrt(T).
        v_phi = np.asarray(self._value_b(
            self.critic, feat.reshape(B * K, N, F),
            left.reshape(B * K, N), right.reshape(B * K, N),
            mask.reshape(B * K, N))).reshape(B, K)
        q = np.zeros((B, K - 1), np.float32)
        for bi in range(B):
            for t in range(ks[bi]):
                v_next = v_phi[bi, t + 1] if t + 1 < n_states_b[bi] \
                    else term_b[bi]
                q[bi, t] = rs_b[bi][t] + v_next - v_phi[bi, t]

        T = B * (K - 1)
        batch = {"feat": feat[:, :-1].reshape(T, N, F),
                 "left": left[:, :-1].reshape(T, N),
                 "right": right[:, :-1].reshape(T, N),
                 "mask": mask[:, :-1].reshape(T, N),
                 "amask": amask.reshape(T, -1), "action": action.reshape(T),
                 "old_logp": old_logp.reshape(T),
                 "q": jnp.asarray(q.reshape(T)), "valid": tvalid.reshape(T)}
        sbatch = {"feat": feat.reshape(B * K, N, F),
                  "left": left.reshape(B * K, N),
                  "right": right.reshape(B * K, N),
                  "mask": mask.reshape(B * K, N),
                  "v_target": jnp.asarray(v_pi.reshape(B * K)),
                  "valid": svalid.reshape(B * K)}
        (self.actor, self.critic, self.aopt, self.copt,
         al, cl) = self._update_epochs(self.actor, self.critic, self.aopt,
                                       self.copt, batch, sbatch)
        return {"actor_loss": float(al), "critic_loss": float(cl)}

    def param_count(self) -> int:
        return sum(int(np.prod(x.shape)) for x in
                   jax.tree_util.tree_leaves((self.actor, self.critic)))

    def clone(self, seed: int = 0) -> "AqoraAgent":
        """A fresh agent (own jit caches, own PRNG chain) carrying a deep
        COPY of this agent's params + optimizer state. The online
        `learn.BackgroundLearner` trains a clone so its donated update
        buffers can never alias the serving agent's params."""
        from repro.checkpoint import agent_state, install_agent_state
        other = type(self)(self.meta, self.cfg, seed=seed)
        install_agent_state(other, agent_state(self), copy=True)
        return other
