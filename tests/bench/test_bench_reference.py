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


# -------------------------------------------- composite keys, packed
class _UniqueRowsCounter(reference.JoinCounter):
    """The counter with the composite numbering it had before keys were
    packed: `np.unique(axis=0)` over both sides' stacked tuples, for every
    edge of more than one column, in every count."""

    def _edge_keys(self, a, a_cols, p, p_cols):
        if len(a_cols) == 1:
            return self._key(a, a_cols[0]), self._key(p, p_cols[0])
        a_tup = np.stack([self._key(a, c) for c in a_cols], axis=1)
        p_tup = np.stack([self._key(p, c) for c in p_cols], axis=1)
        _, codes = np.unique(np.concatenate([a_tup, p_tup]), axis=0,
                             return_inverse=True)
        codes = codes.reshape(-1)
        return codes[:len(a_tup)], codes[len(a_tup):]


# the key columns' values: a small domain (many duplicate tuples), one
# that holds negative values, and one whose spans multiply past 2**63
VALUES = {"small": lambda rng, n: rng.integers(0, 5, n),
          "negative": lambda rng, n: rng.integers(-4, 3, n),
          "huge": lambda rng, n: rng.choice(
              np.array([-2 ** 62, -1, 0, 7, 2 ** 62 - 1]), n)}


def _keyed_world(seed: int, values: str, empty: bool = False):
    """Four tables of a few hundred rows, three key columns of `values`
    each and a filter column; with `empty`, c's filter selects no row."""
    rng = np.random.default_rng(seed)
    tables = {f"t{a}": {**{f"k{i}": VALUES[values](rng, int(n))
                           for i in range(3)},
                        "f": rng.integers(0, 10, int(n))}
              for a, n in zip(ALIASES, rng.integers(200, 400, 4))}
    filters = [[("f", "<=", (7,))], [], [("f", ">=", (10 if empty else 2,))],
               [("f", "in", (1, 3, 4, 8))]]
    return tables, [(a, f"t{a}", f) for a, f in zip(ALIASES, filters)]


def _calls(monkeypatch, name: str) -> List[int]:
    """A list that grows by one at each call of `reference.<name>`."""
    calls: List[int] = []
    real = getattr(reference, name)
    monkeypatch.setattr(reference, name,
                        lambda *a: calls.append(1) or real(*a))
    return calls


@pytest.mark.parametrize("cols", [1, 2, 3])
@pytest.mark.parametrize("values", sorted(VALUES))
def test_tuple_codes_are_unique_rows_codes(monkeypatch, values, cols):
    rng = np.random.default_rng(cols)
    tup = [VALUES[values](rng, 500) for _ in range(cols)]
    fell_back = _calls(monkeypatch, "_unique_rows")
    got = reference.tuple_codes(tup)
    _, want = np.unique(np.stack(tup, axis=1), axis=0, return_inverse=True)
    np.testing.assert_array_equal(got, want.reshape(-1))
    assert bool(fell_back) == (values == "huge")
    assert len(reference.tuple_codes([t[:0] for t in tup])) == 0


@pytest.mark.parametrize("empty", [False, True])
@pytest.mark.parametrize("widths", [(2, 2, 2), (3, 3, 3), (3, 2, 3)])
@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("values", sorted(VALUES))
def test_packed_counts_equal_unique_rows_counts(monkeypatch, values, shape,
                                                widths, empty):
    """Every connected subset of chains and stars whose edges join on two
    or three columns counts what the `np.unique(axis=0)` numbering counts,
    on keys with duplicates, negative values, an empty side, and spans
    past 2**63 (which take the fallback)."""
    fell_back = _calls(monkeypatch, "_unique_rows")
    conds = _conds(shape, widths)
    nonzero = 0
    for seed in range(2):
        tables, rels = _keyed_world(seed, values, empty)
        new = reference.JoinCounter(tables, rels, conds)
        old = _UniqueRowsCounter(tables, rels, conds)
        for subset in _connected_subsets(SHAPES[shape]):
            want = old.count(subset)
            assert new.count(subset) == want, (seed, subset)
            nonzero += len(subset) > 1 and want > 0
    assert nonzero >= 2     # an empty c leaves a chain only a-b to match
    assert bool(fell_back) == (values == "huge")


# ---------------------------------------------------- the shared memo
def _pool_counts(tables, queries, memo):
    """Each query's join of each growing prefix of its relations in
    breadth-first order, by counters that share `memo` (None: each its
    own)."""
    out = []
    for q in queries:
        counter = reference.query_counter(tables, q, memo)
        adj = q.adjacency()
        order, todo = [q.relations[0].alias], [q.relations[0].alias]
        while todo:
            for b in adj[todo.pop(0)]:
                if b not in order:
                    order.append(b)
                    todo.append(b)
        out += [counter.count(order[:k]) for k in range(1, len(order) + 1)]
    return out


@pytest.mark.parametrize("bench,make", [("job", "make_job_like"),
                                        ("stack", "make_stack_like")])
def test_a_shared_memo_counts_the_same(bench, make):
    from repro.sql import datagen, workloads
    db = getattr(datagen, make)(scale=0.05, seed=0)
    tables = {name: t.columns for name, t in db.tables.items()}
    wl = workloads.make_workload(bench, n_train=12, n_test_per_template=1,
                                 seed=7)
    queries = wl.train + wl.test
    memo = {}
    shared = _pool_counts(tables, queries, memo)
    assert shared == _pool_counts(tables, queries, None)
    assert len(shared) > 100 and any(shared)
    # one selection per distinct (table, filters), not one per relation
    rels = {(r.table, tuple((f.column, f.op, tuple(f.value))
                            for f in r.filters))
            for q in queries for r in q.relations}
    assert sum(k[0] == "rows" for k in memo) == len(rels)


def _sales_returns(seed: int = 3):
    """Sales and returns joined on (ticket, item), a return copying a
    sale's pair, and an item dimension."""
    rng = np.random.default_rng(seed)
    sales = {"ticket": np.arange(2000) // 5,
             "item": rng.integers(0, 60, 2000),
             "qty": rng.integers(1, 6, 2000)}
    src = rng.integers(0, 2000, 400)
    returns = {"ticket": sales["ticket"][src], "item": sales["item"][src],
               "qty": rng.integers(1, 6, 400)}
    return {"sales": sales, "returns": returns,
            "item": {"cat": rng.integers(0, 5, 60)}}


def _sales_returns_counter(tables, memo, cat, qty=None,
                           cls=reference.JoinCounter):
    ret_filters = [("qty", ">=", (qty,))] if qty else []
    return cls(tables, [
        ("ss", "sales", []), ("sr", "returns", ret_filters),
        ("i", "item", [("cat", "<=", (cat,))])], [
        ("ss", "ticket", "sr", "ticket"), ("ss", "item", "sr", "item"),
        ("ss", "item", "i", "id")], memo)


def test_an_unfiltered_composite_edge_is_numbered_once(monkeypatch):
    numbered = _calls(monkeypatch, "tuple_codes")
    tables, memo = _sales_returns(), {}
    for cat in range(4):            # other dimension filters, and both
        for subset in (["ss", "sr"], ["sr", "ss", "i"], ["i", "ss"]):
            got = _sales_returns_counter(tables, memo, cat).count(subset)
            assert got == _sales_returns_counter(tables, None, cat).count(
                subset)             # directions of the composite edge
    assert sum(numbered) == 1 + 4 * 2   # the shared edge once; memo-less
    #                                     counters number it for themselves
    assert _sales_returns_counter(tables, memo, 0).count(["ss", "sr"]) > 0


def test_a_filtered_side_is_numbered_apart(monkeypatch):
    numbered = _calls(monkeypatch, "tuple_codes")
    tables, memo = _sales_returns(), {}
    counts = {}
    for qty in (None, 3, None, 3, 5):
        counts[qty] = _sales_returns_counter(tables, memo, 2, qty).count(
            ["ss", "sr", "i"])
        assert counts[qty] == _sales_returns_counter(
            tables, None, 2, qty, _UniqueRowsCounter).count(["ss", "sr", "i"])
    assert sum(numbered) == 3       # one per distinct filter on returns
    assert counts[None] > counts[3] > counts[5] > 0
