"""What decides `correct`: the window's own outputs against the references.

Numbers compared (each has a limit in `bench/limits.json`):

* `missing_answers` - queries admitted in the window that never completed
  or whose chunk raised (limit 0);
* `wrong_answers` - completed queries whose result row count, or sampled
  intermediate stages whose row count, differs from `JoinCounter` on the
  same tables (limit 0: the engine is exact, so any plan gives the same
  rows);
* `logp_err` - over every decision the window made, the widest gap
  between the log-probability the policy call returned for its action and
  the float32 reference's, as a share of the decision's logit scale
  (the largest legal |logit|, at least 1);
* `greedy_misses` - decisions made with the policy frozen whose action is
  not the reference's best legal action, where the reference's gap
  between the two, over the logit scale, exceeds the `logp_err` limit
  (nearer ties are a matter of rounding) (limit 0);
* `loss_err`, `grad_err`, `update_err` - the training cell's first three
  PPO updates against the reference's on the same batches (`update_gaps`).
"""
from __future__ import annotations

import json
from collections import defaultdict
from pathlib import Path
from typing import Dict, List

import numpy as np

from bench import reference

LIMITS = Path(__file__).resolve().parent / "limits.json"
STAGE_SAMPLE = 64          # intermediate stages checked per run, drawn
#                            from the seed (every final result is checked)


def limits() -> Dict[str, float]:
    return json.loads(LIMITS.read_text())


def answers(db, comps, attempted: int, rng: np.random.Generator) -> Dict:
    """`missing_answers`, `wrong_answers` and how many were checked."""
    tables = {name: t.columns for name, t in db.tables.items()}
    wrong = checked = 0
    stages = []
    for c in comps:
        if c.result.failed:
            continue
        counter = reference.query_counter(tables, c.query)
        want = counter.count(r.alias for r in c.query.relations)
        wrong += int(c.result.stages[-1].out_rows != want)
        checked += 1
        stages += [(counter, s) for s in c.result.stages[:-1]]
    pick = rng.permutation(len(stages))[:STAGE_SAMPLE]
    for i in pick:
        counter, s = stages[i]
        wrong += int(s.out_rows != counter.count(s.covered))
    return {"missing_answers": attempted - len(comps),
            "wrong_answers": wrong, "answers_checked": checked,
            "stages_checked": len(pick)}


def _policy_rows(calls: List[Dict], actors: Dict[int, object],
                 block: int):
    """Blocks of real decisions, grouped by the parameters that served
    them: (host actor, feat, left, right, mask, amask, action, logp,
    explore). `actors` maps an actor's id to (actor, its host copy)."""
    by_actor = defaultdict(list)
    for c in calls:
        by_actor[c["actor"]].append(c)
    for aid, cs in by_actor.items():
        actor = actors[aid][1]
        real = np.concatenate([c["real"] for c in cs])
        cols = [np.concatenate([c["inputs"][i] for c in cs])[real]
                for i in range(5)]
        cols += [np.concatenate([c[k] for c in cs])[real]
                 for k in ("action", "logp")]
        cols.append(np.concatenate([np.full(len(c["real"]), c["explore"])
                                    for c in cs])[real])
        for s in range(0, len(cols[0]), block):
            yield (actor,) + tuple(x[s:s + block] for x in cols)


def _reference_logp(precision: str):
    """Jitted (actor, feat, left, right, mask, amask) -> (logp, scale):
    the reference's masked log-probabilities and each row's logit scale
    (the largest legal |logit|, at least 1)."""
    import jax
    import jax.numpy as jnp

    def fn(actor, f, l, r, m, am):
        lg = reference.policy_logits(actor, f, l, r, m, precision=precision)
        scale = jnp.maximum(1.0, jnp.max(jnp.where(am > 0, jnp.abs(lg),
                                                   0.0), axis=1))
        return reference.masked_logp(lg, am), scale
    return jax.jit(fn)


def policy_checks(calls: List[Dict], actors: Dict[int, object], *,
                  tie: float, control: str = "", block: int = 256) -> Dict:
    """`logp_err`: per real decision, |logp at the returned action - the
    float32 reference's| / the reference's logit scale, the widest.
    `greedy_misses`: frozen decisions whose action is not the reference's
    best legal one by more than `tie` of the logit scale. The reference
    runs on the default device at `highest` precision, with the
    parameters that were serving when the call was made. With `control`
    set (a precision of `reference.policy_logits`), the reference computed
    at that precision stands in for the program: its logp at the returned
    action, and its own best action where the policy was frozen."""
    ref = _reference_logp("highest")
    ctl = _reference_logp(control) if control else None
    gaps, misses, frozen = [], 0, 0
    for actor, f, l, r, m, am, action, logp, explore in _policy_rows(
            calls, actors, block):
        rows = np.arange(len(action))
        ref_logp, scale = (np.asarray(x) for x in ref(actor, f, l, r, m, am))
        if ctl is not None:
            ctl_logp = np.asarray(ctl(actor, f, l, r, m, am)[0])
            logp = ctl_logp[rows, action]
            action = np.where(explore, action,
                              np.argmax(np.where(am > 0, ctl_logp, -np.inf),
                                        axis=1))
        gaps.append(np.abs(logp - ref_logp[rows, action]) / scale)
        best = np.max(np.where(am > 0, ref_logp, -np.inf), axis=1)
        short = (best - ref_logp[rows, action]) / scale
        misses += int(((short > tie) & ~explore).sum())
        frozen += int((~explore).sum())
    return {"logp_err": float(np.concatenate(gaps).max()) if gaps else 0.0,
            "greedy_misses": misses, "frozen_decisions": frozen}


def replay(tape, hp: Dict, precision: str) -> Dict:
    """The reference's PPO updates over the tape's batches, from the
    tape's state before the first: each update's losses, the optimizer's
    first moments after the first, the parameters after the last."""
    import jax

    step = jax.jit(lambda st, b, sb: reference.ppo_update(
        st, b, sb, hp, precision=precision))
    state, losses, first = tape.before, [], None
    for s in tape.steps:
        state, al, cl = step(state, s["batch"], s["sbatch"])
        losses.append((float(al), float(cl)))
        if first is None:
            first = jax.device_get({"aopt": state["aopt"],
                                    "copt": state["copt"]})
    last = jax.device_get({"actor": state["actor"],
                           "critic": state["critic"]})
    return {"losses": losses, "first": first, "last": last}


def program_updates(tape) -> Dict:
    """The tape's record of the program's own updates, as `replay` gives
    the reference's."""
    return {"losses": [(s["actor_loss"], s["critic_loss"])
                       for s in tape.steps],
            "first": tape.after_first, "last": tape.after_last}


def _leaf_norms(tree) -> np.ndarray:
    import jax
    return np.array([float(np.linalg.norm(np.asarray(x, np.float64)))
                     for x in jax.tree_util.tree_leaves(tree)])


def update_gaps(got: Dict, ref: Dict, before: Dict) -> Dict:
    """`loss_err`: the widest gap of a returned loss, over |ref| (at least
    1). `grad_err`: per leaf, the gap between the norms of the optimizer's
    first moment after the first update (what the optimizer got of the
    gradients), over the reference's norm of that leaf or of the median
    leaf, whichever is larger; the worst leaf. `update_err`: the same of
    the parameters' change over all the updates. Leaves whose reference
    moment is under a thousandth of the median leaf's (a gradient that is
    nought but for rounding) are left out of both."""
    loss = max(abs(g - r) / max(1.0, abs(r))
               for gl, rl in zip(got["losses"], ref["losses"])
               for g, r in zip(gl, rl))
    m_got = _leaf_norms([got["first"]["aopt"]["m"], got["first"]["copt"]["m"]])
    m_ref = _leaf_norms([ref["first"]["aopt"]["m"], ref["first"]["copt"]["m"]])
    keep = m_ref >= 1e-3 * np.median(m_ref)

    def change(params):
        import jax
        return jax.tree_util.tree_map(
            lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
            [params["actor"], params["critic"]],
            [before["actor"], before["critic"]])

    d_got, d_ref = _leaf_norms(change(got["last"])), _leaf_norms(
        change(ref["last"]))

    def worst(a, b):
        den = np.maximum(b, np.median(b[keep]))
        return float((np.abs(a - b) / den)[keep].max())
    return {"loss_err": float(loss), "grad_err": worst(m_got, m_ref),
            "update_err": worst(d_got, d_ref),
            "leaves_left_out": int((~keep).sum())}


def verdict(numbers: Dict[str, float], lims: Dict[str, float]) -> bool:
    return all(numbers[k] <= lims[k] for k in numbers)
