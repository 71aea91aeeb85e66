"""Plain references the benchmark holds the program to.

Nothing here imports the program. Each reference restates the semantics
from the paper and the query model, in the plainest form that is fast
enough to run after every window:

* `JoinCounter` - the number of rows an inner equi-join over a connected,
  acyclic set of relations returns, with each relation's filters applied
  first. Two relations may be joined on several columns at once (a
  composite key, as TPC-DS joins a return to its sale): the conditions
  between one pair of relations are one edge of the join tree, and it is
  the edges that must form a tree. It never materialises a row: it passes
  per-key row counts from the leaves of the join tree to its root (a
  counting Yannakakis pass), a composite key first numbered in a domain
  both sides share (`tuple_codes`), so its cost is near linear in the
  table sizes whatever join order a plan would choose. Any plan of an
  exact engine gives this many rows. Counters that share a memo select
  each (table, filters) and number each composite edge once between
  them.
* `tree_net` - the tree-CNN actor's logits, or the critic's value: three
  binary tree convolutions (self, left child, right child weights plus a
  bias, leaky ReLU, padding re-zeroed), a residual around the third, a
  max-pool over real nodes, and a two-layer MLP head.
* `ppo_update` - Alg. 1's PPO update: epochs of the clipped surrogate
  with an entropy bonus for the actor and the squared error for the
  critic, each stepped by Adam with global-norm clipping.

The matmul precision is an argument throughout: `highest` gives the
float32 reference, `high` (or `bf16x3`, the same product written out) the
lower-precision control.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

import numpy as np

LEAKY_SLOPE = 0.01      # jax.nn.leaky_relu's default negative slope
MASKED_LOGIT = -1e9     # logit given to actions the mask forbids


# ------------------------------------------------------------ join counts
def _filter_mask(col: np.ndarray, op: str, value: Tuple) -> np.ndarray:
    if op == "<=":
        return col <= value[0]
    if op == ">=":
        return col >= value[0]
    if op == "==":
        return col == value[0]
    if op == "in":
        return np.isin(col, np.asarray(value))
    raise ValueError(f"unknown filter operator {op!r}")


def _column(columns: Mapping[str, np.ndarray], nrows: int,
            name: str) -> np.ndarray:
    if name in columns:
        return columns[name]
    if name == "id":                    # implicit primary key: row number
        return np.arange(nrows, dtype=np.int64)
    raise KeyError(name)


def _unique_rows(cols: Sequence[np.ndarray]) -> np.ndarray:
    """`tuple_codes` by sorting the tuples themselves, for any columns."""
    _, codes = np.unique(np.stack(cols, axis=1), axis=0, return_inverse=True)
    return codes.reshape(-1)


def tuple_codes(cols: Sequence[np.ndarray]) -> np.ndarray:
    """Dense codes 0..u-1 of the rows of k equally long columns, in the
    lexicographic order of their tuples: equal tuples, equal codes. Integer
    columns whose spans (max - min + 1) multiply to under 2**63 are packed
    into one int64 in mixed radix, the first column most significant, and
    numbered by a 1-D sort; other columns sort the tuples themselves. Both
    give the same codes."""
    n = len(cols[0])
    if n == 0:
        return np.zeros(0, np.int64)
    if not all(np.can_cast(c.dtype, np.int64) for c in cols):
        return _unique_rows(cols)
    lows = [int(c.min()) for c in cols]
    spans = [int(c.max()) - lo + 1 for c, lo in zip(cols, lows)]
    if math.prod(spans) >= 2 ** 63:
        return _unique_rows(cols)
    packed = np.zeros(n, np.int64)
    for c, lo, span in zip(cols, lows, spans):
        packed = packed * span + (c.astype(np.int64) - lo)
    _, codes = np.unique(packed, return_inverse=True)
    return codes.reshape(-1)


class JoinCounter:
    """Row counts of joins over one query's relations.

    `tables` maps a table name to its columns (name -> 1-D int array).
    `relations` is a sequence of (alias, table, filters) with filters as
    (column, op, value) triples; `conds` of (left_alias, left_col,
    right_alias, right_col) equalities. Several conditions between the
    same two aliases join them on a composite key.

    `memo` is a dict the caller may share between counters over the same,
    unchanging `tables`: it keeps the rows each (table, filters) selects
    and each composite edge's numbered keys, by both sides' (table,
    filters, columns), so that such an edge is numbered once among all of
    them. Without one, the counter keeps its own."""

    def __init__(self, tables: Mapping[str, Mapping[str, np.ndarray]],
                 relations: Sequence[Tuple[str, str, Sequence]],
                 conds: Sequence[Tuple[str, str, str, str]],
                 memo: Optional[Dict] = None):
        self._tables = tables
        self._rel = {a: (t, tuple(f)) for a, t, f in relations}
        self._conds = list(conds)
        self._memo = {} if memo is None else memo

    def _selected(self, alias: str) -> np.ndarray:
        """Indices of the rows of `alias` that pass its filters."""
        key = ("rows",) + self._rel[alias]
        if key not in self._memo:
            table, filters = self._rel[alias]
            cols = self._tables[table]
            n = len(next(iter(cols.values())))
            keep = np.ones(n, bool)
            for column, op, value in filters:
                keep &= _filter_mask(_column(cols, n, column), op, value)
            self._memo[key] = np.flatnonzero(keep)
        return self._memo[key]

    def _key(self, alias: str, column: str) -> np.ndarray:
        table, _ = self._rel[alias]
        cols = self._tables[table]
        n = len(next(iter(cols.values())))
        return _column(cols, n, column)[self._selected(alias)]

    def _edges(self, aliases: List[str]) -> Dict[Tuple[str, str], List]:
        """The conditions inside `aliases` grouped by unordered pair of
        relations: (a, b) with a < b -> [(a's column, b's column), ...].
        The pairs must form a spanning tree of `aliases`."""
        inside = set(aliases)
        edges: Dict[Tuple[str, str], List[Tuple[str, str]]] = {}
        for la, lc, ra, rc in self._conds:
            if la in inside and ra in inside:
                if la > ra:
                    la, lc, ra, rc = ra, rc, la, lc
                edges.setdefault((la, ra), []).append((lc, rc))
        group = {a: a for a in aliases}      # union-find over the pairs

        def root(a):
            while group[a] != a:
                a = group[a]
            return a
        for a, b in edges:
            ra, rb = root(a), root(b)
            if ra == rb:
                raise ValueError(f"join graph over {aliases} has a cycle "
                                 f"through {a} and {b}")
            group[ra] = rb
        if len({root(a) for a in aliases}) != 1:
            raise ValueError(f"join graph over {aliases} is not connected")
        return edges

    def _edge_keys(self, a: str, a_cols: Sequence[str], p: str,
                   p_cols: Sequence[str]) -> Tuple[np.ndarray, np.ndarray]:
        """The join keys of `a` and `p` on one edge. A composite key's
        tuples are numbered in one domain over both sides' tuples, once
        per memo."""
        if len(a_cols) == 1:
            return self._key(a, a_cols[0]), self._key(p, p_cols[0])
        side_a = self._rel[a] + (tuple(a_cols),)
        side_p = self._rel[p] + (tuple(p_cols),)
        if ("edge", side_a, side_p) not in self._memo:
            n = len(self._selected(a))
            codes = tuple_codes([
                np.concatenate([self._key(a, ca), self._key(p, cp)])
                for ca, cp in zip(a_cols, p_cols)])
            self._memo["edge", side_a, side_p] = codes[:n], codes[n:]
            self._memo["edge", side_p, side_a] = codes[n:], codes[:n]
        return self._memo["edge", side_a, side_p]

    def count(self, aliases: Iterable[str]) -> int:
        """Rows of the inner join of `aliases` under the conditions among
        them. The pairs of relations the conditions join must form a
        spanning tree of the set; one pair may be joined on several
        columns."""
        aliases = sorted(set(aliases))
        adj: Dict[str, List[Tuple[str, List[str], List[str]]]] = {
            a: [] for a in aliases}
        for (a, b), cols in self._edges(aliases).items():
            a_cols, b_cols = [c for c, _ in cols], [c for _, c in cols]
            adj[a].append((b, a_cols, b_cols))   # (neighbour, my cols,
            adj[b].append((a, b_cols, a_cols))   #  its cols)
        root = aliases[0]
        order, parent, seen = [], {root: None}, {root}
        stack = [root]
        while stack:
            a = stack.pop()
            order.append(a)
            for b, mine, theirs in adj[a]:
                if b not in seen:
                    seen.add(b)
                    parent[b] = (a, mine, theirs)
                    stack.append(b)
        # w[a][i]: rows of the subtree under a that join with a's row i
        w = {a: np.ones(len(self._selected(a)), np.float64) for a in aliases}
        for a in reversed(order):           # children before parents
            if parent[a] is None:
                continue
            p, p_cols, a_cols = parent[a]
            a_key, p_key = self._edge_keys(a, a_cols, p, p_cols)
            if len(a_key) == 0 or len(p_key) == 0:
                w[p] = np.zeros(len(p_key))
                continue
            if min(a_key.min(), p_key.min()) < 0:
                raise ValueError("negative join key")
            size = int(max(a_key.max(), p_key.max())) + 1
            per_key = np.bincount(a_key, weights=w[a], minlength=size)
            w[p] = w[p] * per_key[p_key]
        total = float(w[root].sum())
        if total >= 2.0 ** 53:
            raise ValueError("join count beyond exact float64 integers")
        return int(total)


def query_counter(tables, query, memo: Optional[Dict] = None) -> JoinCounter:
    """A `JoinCounter` over one query object (`relations` with `alias`,
    `table`, `filters`; `conds` with `left`, `lcol`, `right`, `rcol`, where
    several conditions between two aliases make one composite-key edge),
    sharing `memo` (see `JoinCounter`) where one is given."""
    rels = [(r.alias, r.table, [(f.column, f.op, tuple(f.value))
                                for f in r.filters]) for r in query.relations]
    conds = [(c.left, c.lcol, c.right, c.rcol) for c in query.conds]
    return JoinCounter(tables, rels, conds, memo)


# ------------------------------------------------------------ the policy
def _bf16x3(x):
    """x as the sum of two bfloat16 parts (the split `high` precision
    multiplies with)."""
    import jax.numpy as jnp
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def _matmul(precision: str):
    import jax.numpy as jnp

    def mm(x, w):
        if precision == "bf16x3":
            xh, xl = _bf16x3(x)
            wh, wl = _bf16x3(w)
            return (jnp.matmul(xh, wh, precision="highest")
                    + jnp.matmul(xh, wl, precision="highest")
                    + jnp.matmul(xl, wh, precision="highest"))
        return jnp.matmul(x, w, precision=precision)
    return mm


def _leaky(x):
    import jax.numpy as jnp
    return jnp.where(x >= 0, x, LEAKY_SLOPE * x)


def tree_net(net, feat, left, right, mask, *, precision):
    """Outputs (B, d_out) of one encoder + MLP head network: the actor
    (d_out = actions) or the critic (d_out = 1).

    `net` = {"enc": {"conv1".."conv3": {"wr", "wl", "wrt", "b"}},
    "head": {"w1", "b1", "w2", "b2"}}; feat (B, N, F); left, right (B, N)
    child slots (0 = null child); mask (B, N). `precision` is "highest"
    (float32 products), "high" (the backend's three-pass bfloat16), or
    "bf16x3" (that three-pass product written out, so that it reads the
    same on any backend: hi*hi + hi*lo + lo*hi of two-part bfloat16
    splits, each product exact in float32)."""
    import jax.numpy as jnp
    mm = _matmul(precision)
    m = mask[..., None]

    def conv(p, h):
        hl = jnp.take_along_axis(h, left[..., None], axis=1)
        hr = jnp.take_along_axis(h, right[..., None], axis=1)
        out = mm(h, p["wr"]) + mm(hl, p["wl"]) + mm(hr, p["wrt"]) + p["b"]
        return _leaky(out) * m

    enc, head = net["enc"], net["head"]
    h = conv(enc["conv1"], feat * m)
    h = conv(enc["conv2"], h)
    h = conv(enc["conv3"], h) + h
    pooled = jnp.max(jnp.where(m > 0, h, -jnp.inf), axis=1)
    pooled = jnp.where(jnp.isfinite(pooled), pooled, 0.0)
    hid = _leaky(mm(pooled, head["w1"]) + head["b1"])
    return mm(hid, head["w2"]) + head["b2"]


def policy_logits(actor, feat, left, right, mask, *, precision):
    """Logits (B, d) of the tree-CNN actor (see `tree_net`)."""
    return tree_net(actor, feat, left, right, mask, precision=precision)


def masked_logp(logits, amask):
    """Log-probabilities over the actions the mask allows."""
    import jax
    import jax.numpy as jnp
    return jax.nn.log_softmax(jnp.where(amask > 0, logits, MASKED_LOGIT),
                              axis=-1)


# ------------------------------------------------------------- PPO update
def actor_loss(actor, batch, hp, precision):
    """Alg. 1's clipped surrogate with an entropy bonus, averaged over the
    valid rows of the batch."""
    import jax.numpy as jnp
    lg = tree_net(actor, batch["feat"], batch["left"], batch["right"],
                  batch["mask"], precision=precision)
    logp_all = masked_logp(lg, batch["amask"])
    logp = jnp.take_along_axis(logp_all, batch["action"][:, None], 1)[:, 0]
    ratio = jnp.exp(logp - batch["old_logp"])
    q = batch["q"]
    surrogate = jnp.minimum(ratio * q, jnp.clip(ratio, 1 - hp["clip"],
                                                1 + hp["clip"]) * q)
    valid = batch["valid"]
    n = jnp.maximum(valid.sum(), 1.0)
    neg_entropy = jnp.sum(jnp.where(batch["amask"] > 0,
                                    jnp.exp(logp_all) * logp_all, 0.0), -1)
    return (-jnp.sum(surrogate * valid) / n
            + hp["entropy"] * jnp.sum(neg_entropy * valid) / n)


def critic_loss(critic, sbatch, precision):
    """The critic's squared error against the realized returns, averaged
    over the valid states."""
    import jax.numpy as jnp
    v = tree_net(critic, sbatch["feat"], sbatch["left"], sbatch["right"],
                 sbatch["mask"], precision=precision)[:, 0]
    svalid = sbatch["valid"]
    return (jnp.sum((v - sbatch["v_target"]) ** 2 * svalid)
            / jnp.maximum(svalid.sum(), 1.0))


def _adam(params, grads, opt, lr, hp):
    """Adam with global-norm gradient clipping and bias correction."""
    import jax
    import jax.numpy as jnp
    step = opt["step"] + 1
    leaves = jax.tree_util.tree_leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, hp["grad_clip"] / (norm + 1e-9))
    b1, b2 = hp["b1"], hp["b2"]
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g * scale,
                               opt["m"], grads)
    v = jax.tree_util.tree_map(
        lambda v, g: b2 * v + (1 - b2) * (g * scale) ** 2, opt["v"], grads)
    c1 = 1 - b1 ** step.astype(jnp.float32)
    c2 = 1 - b2 ** step.astype(jnp.float32)
    new = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + hp["eps"])
                                  + hp["weight_decay"] * p), params, m, v)
    return new, {"m": m, "v": v, "step": step}


def ppo_update(state, batch, sbatch, hp, *, precision):
    """One PPO update: `hp["epochs"]` Adam steps of the actor on its loss
    and of the critic on its loss, from `state` = {"actor", "critic",
    "aopt", "copt"}. Returns (new state, last epoch's actor loss, last
    epoch's critic loss)."""
    import jax
    actor, critic, aopt, copt = (state[k] for k in
                                 ("actor", "critic", "aopt", "copt"))
    al = cl = None
    for _ in range(hp["epochs"]):
        al, ag = jax.value_and_grad(actor_loss)(actor, batch, hp, precision)
        cl, cg = jax.value_and_grad(critic_loss)(critic, sbatch, precision)
        actor, aopt = _adam(actor, ag, aopt, hp["lr_actor"], hp)
        critic, copt = _adam(critic, cg, copt, hp["lr_critic"], hp)
    return {"actor": actor, "critic": critic, "aopt": aopt,
            "copt": copt}, al, cl
