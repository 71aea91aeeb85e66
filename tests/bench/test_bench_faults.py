"""A broken timed path, and the control, come out as not correct: the
faults each cell can have, planted underneath the benchmark's own record
of the run, on the CPU at a tiny scale."""
import time

import numpy as np
import pytest

from benchtest import RUN, plan_for, tiny
from bench import checks, harness


def _faulty_run(monkeypatch, fault):
    """job.serve with the timed path broken underneath; returns the line."""
    from repro.core.agent import AqoraAgent
    from repro.sql import executor

    if fault in ("action", "half_batch", "not_greedy"):
        real_act = AqoraAgent.act_batch

        def act_batch(self, feat, left, right, mask, amask, keys,
                      explore=True):
            a, logp, k = real_act(self, feat, left, right, mask, amask,
                                  keys, explore=explore)
            a, logp = a.copy(), logp.copy()
            if fault == "not_greedy":  # the runner-up, with its true logp
                from bench import reference
                lp = np.asarray(reference.masked_logp(
                    reference.policy_logits(self.actor, feat, left, right,
                                            mask, precision="highest"),
                    amask))
                lp = np.where(np.asarray(amask) > 0, lp, -np.inf)
                for i in range(len(a)):
                    order = np.argsort(-lp[i])
                    if np.isfinite(lp[i, order[1]]):
                        a[i], logp[i] = order[1], lp[i, order[1]]
            elif fault == "action":    # a decision altered where made
                legal = np.asarray(amask) > 0
                for i in range(len(a)):
                    others = np.flatnonzero(legal[i])
                    others = others[others != a[i]]
                    if len(others):
                        a[i] = others[0]
            else:                      # lanes past the first half dropped:
                h = len(a) // 2        # they repeat the first half's output
                a[h:], logp[h:] = a[:len(a) - h], logp[:len(a) - h]
            return a, logp, k
        monkeypatch.setattr(AqoraAgent, "act_batch", act_batch)
    elif fault == "raise":             # the window's first chunk raises
        from repro.serve.service import QueryService
        real_run, runs = QueryService.run, []

        def run(self, stream):
            runs.append(1)
            if len(runs) == 2:         # the warm-up chunk, then this
                raise RuntimeError("planted fault")
            return real_run(self, stream)
        monkeypatch.setattr(QueryService, "run", run)
    elif fault == "answer":            # a result altered where produced
        real_join = executor.Executor.join

        def join(self, *args, **kw):
            out, rec = real_join(self, *args, **kw)
            rec.out_rows += 1
            return out, rec
        monkeypatch.setattr(executor.Executor, "join", join)
    plan = tiny(harness.cell_plan(harness.load_spec(), "job.serve"))
    plan["config"]["setup_training"]["episodes"] = 0
    return RUN.run(plan, 11, 1.0, False, time.perf_counter())


@pytest.mark.parametrize("fault", ["action", "half_batch", "answer",
                                   "raise", "not_greedy"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    out = _faulty_run(monkeypatch, fault)
    assert out["correct"] is False
    failed = [k for k, c in out["checks"].items() if c["value"] > c["limit"]]
    assert failed == {"answer": ["wrong_answers"],
                      "raise": ["missing_answers"],
                      "not_greedy": ["greedy_misses"]}.get(
        fault, ["logp_err", "greedy_misses"])


def test_control_is_not_correct():
    """The float32 reference at `high` (three-pass bfloat16, written out so
    that the CPU computes it too) in the program's place fails logp_err."""
    from bench import control
    plan = tiny(harness.cell_plan(harness.load_spec(), "job.serve"))
    line = next(control.readings(plan, [4], 1.0, controls=("bf16x3",)))
    lim = checks.limits()["logp_err"]
    assert line["logp_err"] <= lim < line["control_bf16x3"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_update_is_not_correct(monkeypatch, fault):
    """job.train with the PPO update broken underneath the benchmark's
    record of it: a step that returns its state unchanged, or one that
    leaves out half of the batch and takes the mean over the rest."""
    import jax
    import jax.numpy as jnp
    from repro.core.agent import AqoraAgent
    real_build = AqoraAgent._build_jits

    def build(self):
        real_build(self)
        update = self._update_epochs

        def broken(actor, critic, aopt, copt, batch, sbatch):
            if fault == "unchanged":
                copies = jax.tree_util.tree_map(jnp.copy,
                                                (actor, critic, aopt, copt))
                out = update(*copies, batch, sbatch)
                return (actor, critic, aopt, copt) + tuple(out[4:])
            half = lambda v: np.where(np.arange(len(v)) < len(v) // 2,  # noqa
                                      v, 0.0).astype(np.float32)
            return update(actor, critic, aopt, copt,
                          dict(batch, valid=half(batch["valid"])),
                          dict(sbatch, valid=half(sbatch["valid"])))
        self._update_epochs = broken
    monkeypatch.setattr(AqoraAgent, "_build_jits", build)
    out = RUN.run(plan_for("job", "train"), 5, 1.0, False,
                  time.perf_counter())
    assert out["correct"] is False
    failed = {k for k, c in out["checks"].items() if c["value"] > c["limit"]}
    assert "update_err" in failed
