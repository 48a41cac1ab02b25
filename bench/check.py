"""Whether what the timed window produced is correct.

Every search finished in the window is held to its deployment's rules,
and a sample of them, drawn from the run's seed, to the plain reference
(`reference.py`) at the states the service itself stepped through:

  split_mismatch  Ruya mode: searches whose priority group differs from
                  the §III-D reference split.  Exact, limit 0.
  trial_mismatch  searches with a repeated or out-of-range trial, a trial
                  cost other than its table's, or a scripted initial
                  trial outside the priority group.  Exact, limit 0.
  incomplete      searches that did not finish, did not converge, or made
                  more or fewer trials than the rules allow.  Limit 0.
  pick_gap        widest shortfall of a BO pick's reference EI below the
                  reference's best EI at that state, as a share of the
                  best (floored at EI_FLOOR × best cost).

At a float64 tie of the surrogate's grid (`reference.LML_TIE`) the
service may follow any tied grid point: the smallest reading counts.
Each deployment's configuration holds its pick_gap limit; `PERF.md` gives
the readings each limit was set from.
"""

from __future__ import annotations

import numpy as np
from scipy.special import erf

from reference import (expected_improvement, grid_fits, split, standardize,
                       tied)

# EI differences below this share of the best cost decide nothing: the
# stop rule compares max EI with 0.1 × best.  (chip_smoke's EI_ATOL.)
EI_FLOOR = 1e-5
# Stands for "the pick is not a candidate at all" in pick_gap.
NOT_A_CANDIDATE = 1e6

# The exact numbers' limits; pick_gap's is the deployment's own
# (``check.pick_gap_limit`` in its configuration file).
EXACT = {"split_mismatch": 0, "trial_mismatch": 0, "incomplete": 0}


def budget(n_prio: int, n: int, settings: dict) -> int:
    """Trials a search may make: its whole pool, capped at max_iters
    (never below the scripted initial trials)."""
    n_init = min(settings["n_init"], n_prio)
    if settings["max_iters"] is None:
        return n
    return min(n, max(settings["max_iters"], n_init))


class Checker:
    """Holds one deployment's data and its reference splits."""

    def __init__(self, cfg: dict, data: dict):
        self.cfg = cfg
        self.svc = cfg["service"]
        self.surrogate = cfg["surrogate"]
        self.data = data
        self.enc = standardize(data["features"])
        self.limits = dict(EXACT, pick_gap=cfg["check"]["pick_gap_limit"])
        n = len(self.enc)
        self.n = n
        self.prio = []
        for job in data["jobs"]:
            if self.svc["mode"] == "ruya":
                p = split(job, data["total_memory"], data["num_nodes"])
            else:
                p = list(range(n))
            mask = np.zeros(n, bool)
            mask[p] = True
            self.prio.append(mask)

    # ------------------------------------------------------------ states

    def state(self, search: dict, k: int):
        """(trials, costs, candidate mask) after the first k trials."""
        trials = search["trials"][:k]
        seen = np.zeros(self.n, bool)
        seen[trials] = True
        prio = self.prio[search["job"]]
        cand = prio & ~seen
        if not cand.any():
            cand = ~prio & ~seen
        cost = self.data["jobs"][search["job"]]["cost"]
        return trials, cost[trials], cand

    def reference(self, trials, costs, cand):
        """EI over the space at each tied grid point."""
        fits = grid_fits(np, self.enc, trials, costs, self.surrogate)
        return [expected_improvement(np, erf, self.enc, costs, fits, h,
                                     cand, self.surrogate)
                for h in tied(fits[0])]

    # ------------------------------------------------------------ sample

    def sample(self, searches: list, seed: int) -> list:
        """States to hold to the reference: every BO step of a seeded
        sample of the distinct finished searches, always with the one of
        most trials.  Returns [(search, k)]: the state before the search's
        trial k."""
        rng = np.random.default_rng([seed, 17])
        done, seen = [], set()
        for s in searches:
            key = (s["job"], tuple(s["trials"]))
            if (s["status"] == "converged" and len(s["trials"]) > s["n_init"]
                    and key not in seen):
                seen.add(key)
                done.append(s)
        if not done:
            return []
        longest = max(range(len(done)), key=lambda i: len(done[i]["trials"]))
        others = [i for i in range(len(done)) if i != longest]
        m = min(len(others), self.cfg["check"]["searches"] - 1)
        picked = [longest] + [int(i) for i in rng.choice(others, m,
                                                          replace=False)]
        return [(done[i], k) for i in picked
                for k in range(done[i]["n_init"], len(done[i]["trials"]))]

    # ----------------------------------------------------------- numbers

    def rules(self, searches: list) -> dict:
        """The exact numbers over every search of the window."""
        settings = self.svc["settings"]
        split_bad = trial_bad = incomplete = 0
        for s in searches:
            if s["status"] is None:
                incomplete += 1
                continue
            prio = self.prio[s["job"]]
            if self.svc["mode"] == "ruya" and (
                    tuple(np.flatnonzero(prio)) != tuple(s["priority"])
                    or tuple(np.flatnonzero(~prio)) != tuple(s["remaining"])):
                split_bad += 1
            tr = s["trials"]
            cost = self.data["jobs"][s["job"]]["cost"]
            if (len(set(tr)) != len(tr)
                    or not all(0 <= i < self.n for i in tr)
                    or any(c != cost[i] for i, c in zip(tr, s["costs"]))
                    or not all(prio[i] for i in tr[:s["n_init"]])):
                trial_bad += 1
                continue
            cap = budget(int(prio.sum()), self.n, settings)
            least = cap if self.svc["to_exhaustion"] else min(
                settings["min_observations"], cap)
            if s["status"] != "converged" or not least <= len(tr) <= cap:
                incomplete += 1
        return {"split_mismatch": split_bad, "trial_mismatch": trial_bad,
                "incomplete": incomplete}

    def readings(self, states: list, answer) -> dict:
        """pick_gap over ``states``.  ``answer(search, k, trials, costs,
        cand)`` gives the pick of the system held to the reference at that
        state: the service's own trial (`service_answer`), or the
        control's."""
        gap = 0.0
        for s, k in states:
            trials, costs, cand = self.state(s, k)
            pick = answer(s, k, trials, costs, cand)
            floor = EI_FLOOR * abs(float(np.min(costs)))
            gap = max(gap, min(_gap(ei, pick, floor)
                               for ei in self.reference(trials, costs, cand)))
        return {"pick_gap": gap}


def _gap(ei, pick, floor) -> float:
    top = float(np.max(ei))
    if not 0 <= pick < len(ei) or not np.isfinite(ei[pick]):
        return NOT_A_CANDIDATE
    return (top - float(ei[pick])) / max(top, floor)


def service_answer(s, k, trials, costs, cand):
    """The service's answer at the state before its trial k: that trial."""
    return s["trials"][k]


def verdict(numbers: dict, limits: dict) -> bool:
    return all(numbers[name] <= limit for name, limit in limits.items()
               if name in numbers)
