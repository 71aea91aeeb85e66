"""The harness's answer check on joins over composite keys: a small world of
two fact tables, sales and returns, whose returns copy a sale's (ticket,
item) as TPC-DS's store_returns copy a store_sales row, and a dimension.
Queries joining a return to its sale on both columns are served through
`QueryService` with the program's own executor; `checks.answers` reads
every completed answer and sampled stage as right, and one off by a row
as wrong."""
import numpy as np
import pytest

from benchtest import ROOT  # noqa: F401  (puts the repository on sys.path)
from bench import checks, reference

N_SALES, N_RETURNS, N_ITEMS = 600, 150, 40


def _world():
    from repro.sql.catalog import Database, Table, analyze
    rng = np.random.default_rng(5)
    sales = {"ticket": np.arange(N_SALES, dtype=np.int64) // 4,
             "item": rng.integers(0, N_ITEMS, N_SALES),
             "customer": rng.integers(0, 50, N_SALES)}
    # a return copies one sale's (ticket, item), some sales twice; a few
    # returns name a ticket and item that were never sold together
    src = rng.integers(0, N_SALES, N_RETURNS)
    returns = {"ticket": sales["ticket"][src].copy(),
               "item": sales["item"][src].copy(),
               "qty": rng.integers(1, 5, N_RETURNS)}
    stray = rng.random(N_RETURNS) < 0.1
    returns["item"][stray] = (returns["item"][stray] + 1) % N_ITEMS
    item = {"category": rng.integers(0, 6, N_ITEMS)}
    db = Database("sales_returns", {
        name: Table(name, cols) for name, cols in
        (("sales", sales), ("returns", returns), ("item", item))})
    db.stats = analyze(db)
    return db


def _queries():
    from repro.sql.query import Filter, JoinCond, Query, Relation
    composite = (JoinCond("ss", "ticket", "sr", "ticket"),
                 JoinCond("ss", "item", "sr", "item"))
    out = []
    for k in range(6):
        dim = Relation("i", "item", (Filter("category", "<=", (k % 4 + 1,)),))
        fact = Relation("sr", "returns", (Filter("qty", ">=", (k % 3 + 1,)),))
        rels = (Relation("ss", "sales"), fact, dim)
        if k % 2:                       # another order of the same joins
            rels = (dim, fact, Relation("ss", "sales"))
        out.append(Query(f"sales_returns#{k}", rels,
                         composite + (JoinCond("ss", "item", "i", "id"),)))
    return out


@pytest.fixture(scope="module")
def served():
    from repro.core.agent import AgentConfig, AqoraAgent
    from repro.core.encoding import WorkloadMeta
    from repro.serve.scheduler import Arrival
    from repro.serve.service import QueryService
    from repro.sql.cbo import Estimator
    from repro.sql.workloads import Workload
    db, queries = _world(), _queries()
    wl = Workload("sales_returns", 3, queries, [])
    agent = AqoraAgent(WorkloadMeta.from_workload(wl), AgentConfig(), seed=0)
    svc = QueryService(db, agent, est=Estimator(db, db.stats), n_lanes=4)
    stream = [Arrival(0.5 * i, query=q, seed=i)
              for i, q in enumerate(queries)]
    comps, _ = svc.run(stream)
    return db, queries, comps


def test_the_composite_key_is_not_the_single_column_join(served):
    """The world is one where joining on the item alone gives other
    counts: a check that dropped the second column would not pass."""
    db, queries, _ = served
    tables = {n: t.columns for n, t in db.tables.items()}
    rels = [("ss", "sales", []), ("sr", "returns", [])]
    both = reference.JoinCounter(tables, rels, [
        ("ss", "ticket", "sr", "ticket"), ("ss", "item", "sr", "item")])
    item_only = reference.JoinCounter(tables, rels, [
        ("ss", "item", "sr", "item")])
    assert 0 < both.count(["ss", "sr"]) < item_only.count(["ss", "sr"])


def test_composite_key_answers_are_right(served):
    db, queries, comps = served
    assert len(comps) == len(queries)
    assert not any(c.result.failed for c in comps)
    got = checks.answers(db, comps, len(queries), np.random.default_rng(0))
    assert got["wrong_answers"] == 0 and got["missing_answers"] == 0
    assert got["answers_checked"] == len(queries)
    assert got["stages_checked"] >= len(queries)
    assert all(c.result.stages[-1].out_rows > 0 for c in comps)


def test_an_answer_off_by_a_row_is_wrong(served):
    import copy
    db, queries, comps = served
    comps = copy.deepcopy(comps)
    comps[0].result.stages[-1].out_rows += 1
    got = checks.answers(db, comps, len(queries), np.random.default_rng(0))
    assert got["wrong_answers"] >= 1


def test_a_shared_memo_counts_the_same(served):
    """Counters sharing one memo, as `checks.answers` builds them, count
    every stage of every served query as counters with their own do, and
    as the program's exact executor did."""
    db, queries, comps = served
    tables = {n: t.columns for n, t in db.tables.items()}
    memo = {}
    for c in comps:
        shared = reference.query_counter(tables, c.query, memo)
        alone = reference.query_counter(tables, c.query)
        for s in c.result.stages:
            assert shared.count(s.covered) == alone.count(s.covered) \
                == s.out_rows, (c.query.name, s.covered)
    assert sum(k[0] == "edge" for k in memo) >= 2


def test_the_check_reports_its_seconds(served):
    db, queries, comps = served
    got = checks.answers(db, comps, len(queries), np.random.default_rng(1))
    assert 0 < got["answers_s"] < 60
    assert "answers_s" not in checks.limits()
