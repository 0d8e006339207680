"""Successive halving and HyperBand's schedule, written out again in numpy.

Nothing here imports the program. The formulas are HpBandSter's
(Li et al. 2018; Falkner, Klein, Hutter 2018, ``hpbandster/optimizers/
hyperband.py``): with ``K = floor(log_eta(max/min)) + 1`` rungs, iteration
``i`` runs bracket ``s = K - 1 - (i mod K)`` with ``n0 = ceil(K/(s+1) *
eta**s)`` configurations, ``n_j = max(floor(n0 * eta**-j), 1)`` at rung
``j``, on the last ``s + 1`` budgets of the geometric ladder.
"""

import math

import numpy as np


def budget_ladder(min_budget, max_budget, eta):
    k = int(math.floor(math.log(max_budget / min_budget) / math.log(eta) + 1e-9)) + 1
    return [max_budget * float(eta) ** (-j) for j in range(k - 1, -1, -1)]


def hyperband_plan(iteration, min_budget, max_budget, eta):
    """``(num_configs, budgets)`` of HyperBand's iteration ``iteration``."""
    ladder = budget_ladder(min_budget, max_budget, eta)
    k = len(ladder)
    s = k - 1 - (iteration % k)
    n0 = int(math.ceil((k / (s + 1)) * eta ** s))
    counts = [max(int(n0 * eta ** (-j)), 1) for j in range(s + 1)]
    return counts, ladder[-(s + 1):]


def mesh_aligned_plan(n_configs, min_budget, max_budget, eta, n_shards):
    """One deep bracket whose every rung is a multiple of ``n_shards``,
    rounded up, and never wider than the rung before it."""
    ladder = budget_ladder(min_budget, max_budget, eta)
    counts = []
    for j in range(len(ladder)):
        n = max(int(n_configs * float(eta) ** (-j)), 1)
        counts.append(max(-(-n // n_shards) * n_shards, n_shards))
    for j in range(len(counts) - 2, -1, -1):
        counts[j] = max(counts[j], counts[j + 1])
    return counts, ladder


def schedule(config, traffic, chips):
    """The brackets one sweep of this cell runs: a list of
    ``(num_configs, budgets)``."""
    lo, hi, eta = config["min_budget"], config["max_budget"], config["eta"]
    if traffic["entry"] == "sharded":
        plan = mesh_aligned_plan(traffic["n_configs"], lo, hi, eta, chips)
        return [plan] * traffic["n_brackets"]
    return [hyperband_plan(i, lo, hi, eta)
            for i in range(traffic["run"]["n_iterations"])]


def schedule_evaluations(plans):
    return sum(sum(counts) for counts, _ in plans)


def schedule_lane_steps(plans):
    """Lane-SGD-steps where a budget is a cumulative step count and a
    promoted lane trains only the steps it has not had."""
    total = 0
    for counts, budgets in plans:
        prev = 0
        for n, b in zip(counts, budgets):
            total += n * (int(round(b)) - prev)
            prev = int(round(b))
    return total


def promotion_violations(record, plans):
    """Rungs of one sweep whose survivors are not the next rung's count of
    best losses. A crashed lane (NaN) ranks last; ties may go either way,
    so the test is: no survivor is worse than any lane left behind."""
    violations = 0
    for b, (counts, budgets) in enumerate(plans):
        rows = record["bracket"] == b
        lane, budget, loss = (record[k][rows] for k in ("lane", "budget", "loss"))
        for j in range(len(counts)):
            at = np.isclose(budget, budgets[j])
            if at.sum() != counts[j]:
                violations += 1
                continue
            if j + 1 == len(counts):
                break
            nxt = np.isclose(budget, budgets[j + 1])
            rank = np.where(np.isnan(loss[at]), np.inf, loss[at])
            kept = np.isin(lane[at], lane[nxt])
            if kept.sum() != counts[j + 1] or (
                kept.any() and (~kept).any()
                and rank[kept].max() > rank[~kept].min()
            ):
                violations += 1
    return violations


def bookkeeping(records, plans):
    """The comparisons every cell shares, over the sweeps of one window:
    ``[(name, value, limit), ...]`` with ``value <= limit`` sound. A loss is
    a number or NaN (the program's mask for a crash); an infinite loss is, by
    the program's contract, a diverged lane's valid worst result."""
    expected = schedule_evaluations(plans)
    wrong_count = all_crashed = rises = violations = not_least = 0
    for rec in records:
        if rec["kind"] == "runs":
            loss = rec["loss"]
            wrong_count += rec["evaluations"] != expected or len(loss) != expected
            all_crashed += bool(np.isnan(loss).all())
            t = np.asarray(rec["trajectory"], float)
            rises += bool(len(t) == 0 or (np.diff(t) > 0).any())
            violations += promotion_violations(rec, plans)
        else:
            wrong_count += rec["evaluations"] != expected
            best = np.asarray(rec["per_bracket_loss"], float)
            all_crashed += bool(np.isnan(best).all())
            if not np.isnan(best).all():
                # the incumbent is the least of the per-bracket bests, exactly
                not_least += np.nanmin(best) != rec["incumbent"]["loss"]
    return [
        ("sweeps_with_wrong_evaluation_count", int(wrong_count), 0),
        ("sweeps_all_crashed", int(all_crashed), 0),
        ("incumbent_trajectories_rising", int(rises), 0),
        ("rungs_not_top_k", int(violations), 0),
        ("incumbents_not_least_of_bracket_bests", int(not_least), 0),
    ]
