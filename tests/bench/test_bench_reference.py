"""`reference.JoinCounter` against a nested-loop count, on join trees whose
edges carry one, two or three column pairs (composite keys), and against
its own single-column form on the JOB- and STACK-like worlds."""
import itertools
from typing import Dict, List

import numpy as np
import pytest

from benchtest import ROOT  # noqa: F401  (puts the repository on sys.path)
from bench import reference

ALIASES = ("a", "b", "c", "d")
SHAPES = {"chain": (("a", "b"), ("b", "c"), ("c", "d")),
          "star": (("a", "b"), ("a", "c"), ("a", "d"))}


def _world(seed: int, widths):
    """Four small tables of three key columns over a small domain (so keys
    repeat) and a filter column, a filter on each relation, and the
    conditions of a join tree whose edge j joins on `widths[j]` column
    pairs, each pair naming other columns on its two sides."""
    rng = np.random.default_rng(seed)
    tables = {f"t{a}": {**{f"k{i}": rng.integers(0, 4, int(n))
                           for i in range(3)},
                        "f": rng.integers(0, 10, int(n))}
              for a, n in zip(ALIASES, rng.integers(6, 12, 4))}
    filters = [[("f", "<=", (7,))], [("f", "in", (0, 2, 3, 5, 6, 8, 9))],
               [("f", ">=", (1,))], []]
    rels = [(a, f"t{a}", f) for a, f in zip(ALIASES, filters)]
    return tables, rels


def _conds(shape: str, widths):
    return [(l, f"k{i}", r, f"k{(i + j) % 3}")
            for j, ((l, r), k) in enumerate(zip(SHAPES[shape], widths))
            for i in range(k)]


def _nested_loop(tables, rels, conds, aliases) -> int:
    """Every combination of the filtered rows, kept when all conditions
    among `aliases` hold."""
    aliases = sorted(aliases)
    table = {a: t for a, t, _ in rels}
    rows: Dict[str, List[int]] = {}
    for a, t, filters in rels:
        cols = tables[t]
        rows[a] = [i for i in range(len(cols["f"]))
                   if all(reference._filter_mask(cols[c][i:i + 1], op, v)[0]
                          for c, op, v in filters)]
    inside = [c for c in conds if c[0] in aliases and c[2] in aliases]
    n = 0
    for combo in itertools.product(*(rows[a] for a in aliases)):
        at = dict(zip(aliases, combo))
        n += all(tables[table[la]][lc][at[la]] == tables[table[ra]][rc][at[ra]]
                 for la, lc, ra, rc in inside)
    return n


def _connected_subsets(edges):
    for k in range(1, len(ALIASES) + 1):
        for subset in itertools.combinations(ALIASES, k):
            seen, todo = {subset[0]}, [subset[0]]
            while todo:
                x = todo.pop()
                for l, r in edges:
                    for p, q in ((l, r), (r, l)):
                        if p == x and q in subset and q not in seen:
                            seen.add(q)
                            todo.append(q)
            if len(seen) == k:
                yield subset


@pytest.mark.parametrize("widths", [(1, 1, 1), (2, 2, 2), (3, 3, 3),
                                    (1, 2, 3)])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_counts_equal_a_nested_loop(shape, widths):
    subsets = list(_connected_subsets(SHAPES[shape]))
    assert len(subsets) == {"chain": 10, "star": 11}[shape]
    nonzero = 0
    for seed in range(3):
        tables, rels = _world(seed, widths)
        conds = _conds(shape, widths)
        counter = reference.JoinCounter(tables, rels, conds)
        for subset in subsets:
            want = _nested_loop(tables, rels, conds, subset)
            assert counter.count(subset) == want, (seed, subset)
            nonzero += len(subset) > 1 and want > 0
    assert nonzero >= 6     # the joins match rows, they do not all read 0


@pytest.mark.parametrize("conds", [
    [("a", "k0", "b", "k0"), ("b", "k1", "c", "k1"), ("c", "k2", "a", "k2")],
    [("a", "k0", "b", "k0"), ("a", "k1", "b", "k1"), ("b", "k2", "c", "k2"),
     ("c", "k0", "a", "k0")]])
def test_a_cycle_raises(conds):
    tables, rels = _world(0, (1, 1, 1))
    counter = reference.JoinCounter(tables, rels, conds)
    with pytest.raises(ValueError, match="cycle"):
        counter.count("abc")
    assert counter.count("ab") == _nested_loop(tables, rels, conds, "ab")


class _SingleColumnCounter(reference.JoinCounter):
    """The counter as it was before edges could carry several columns:
    exactly n - 1 conditions over n relations, one column pair each."""

    def count(self, aliases):
        aliases = sorted(set(aliases))
        inside = set(aliases)
        edges = [c for c in self._conds if c[0] in inside and c[2] in inside]
        if len(edges) != len(aliases) - 1:
            raise ValueError(f"join graph over {aliases} is not a tree "
                             f"({len(edges)} conditions)")
        adj = {a: [] for a in aliases}
        for la, lc, ra, rc in edges:
            adj[la].append((ra, lc, rc))
            adj[ra].append((la, rc, lc))
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
        if len(seen) != len(aliases):
            raise ValueError(f"join graph over {aliases} is not connected")
        w = {a: np.ones(len(self._selected(a)), np.float64) for a in aliases}
        for a in reversed(order):
            if parent[a] is None:
                continue
            p, p_col, a_col = parent[a]
            a_key = self._key(a, a_col)
            p_key = self._key(p, p_col)
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


@pytest.mark.parametrize("bench,make", [("job", "make_job_like"),
                                        ("stack", "make_stack_like")])
def test_single_column_counts_are_unchanged(bench, make):
    """Every query's whole join, and the join of each growing prefix of
    its relations in breadth-first order (the sets a plan's stages cover),
    count what the single-column counter counts, to the row."""
    from repro.sql import datagen, workloads
    db = getattr(datagen, make)(scale=0.05, seed=0)
    tables = {name: t.columns for name, t in db.tables.items()}
    wl = workloads.make_workload(bench, n_train=12, n_test_per_template=1,
                                 seed=7)
    checked = 0
    for q in wl.train + wl.test:
        new = reference.query_counter(tables, q)
        old = _SingleColumnCounter(new._tables, [
            (r.alias, r.table, [(f.column, f.op, tuple(f.value))
                                for f in r.filters]) for r in q.relations],
            new._conds)
        adj = q.adjacency()
        order, todo = [q.relations[0].alias], [q.relations[0].alias]
        while todo:
            for b in adj[todo.pop(0)]:
                if b not in order:
                    order.append(b)
                    todo.append(b)
        for k in range(1, len(order) + 1):
            assert new.count(order[:k]) == old.count(order[:k]), (q.name, k)
            checked += 1
    assert checked > 100
