"""The one traffic generator: a mix file and ``--seed`` in, per-row decode
budgets and rewards out.

Every step of a mix has the same set of budgets.  The P * G' rows of a step
get the P * G' quantile midpoints of a log-normal length distribution
(clipped to [min, max]), dealt to the P prompts by a fixed shuffle
(``deal_seed``), so some prompts draw long answers and others short ones.
The run's seed only reorders them: which prompt of the step gets which set,
and which rollout of a prompt gets which budget.  So two seeds do the same
work in another order, and the kept tokens of a step do not depend on the
seed (up to the rare EOS that random weights sample).

Rewards are Bernoulli(p) draws from a hash of (seed, response tokens): with
random weights the mix's own verifier returns 0 for every sample, and all
advantages, and so every update, would be zero.
"""
from __future__ import annotations

import hashlib
import math
import statistics

import numpy as np


def group_sizes(trainer: dict) -> tuple:
    """(P, G, G') of a mix's trainer settings."""
    p, g = trainer["prompts_per_step"], trainer["group_size"]
    return p, g, int(math.ceil(g * trainer["overprovision"]))


def budget_table(mix: dict) -> np.ndarray:
    """(P, G') budgets of every step, before the seed reorders them."""
    p, _, gp = group_sizes(mix["trainer"])
    lengths = mix["lengths"]
    n = p * gp
    nd = statistics.NormalDist()
    mids = [lengths["median"] * math.exp(lengths["sigma"]
                                         * nd.inv_cdf((i + 0.5) / n))
            for i in range(n)]
    lens = np.clip(np.rint(mids), lengths["min"], lengths["max"]).astype(
        np.int32)
    deal = np.random.default_rng(lengths["deal_seed"]).permutation(n)
    return lens[deal].reshape(p, gp)


class Traffic:
    """Budgets of each step, drawn from the run's seed."""

    def __init__(self, mix: dict, seed: int):
        self.table = budget_table(mix)
        self.seed = seed
        self._steps: dict = {}

    def budgets(self, step: int) -> np.ndarray:
        """(P * G',) budgets of ``step``; row r = prompt r // G', rollout
        r % G' (the trainer's ``budget_fn`` row order)."""
        if step not in self._steps:
            rng = np.random.default_rng([self.seed, step])
            t = self.table[rng.permutation(self.table.shape[0])]
            t = np.stack([row[rng.permutation(row.shape[0])] for row in t])
            self._steps[step] = t.reshape(-1)
        return self._steps[step]

    def budget_fn(self, step: int, row: int) -> int:
        return int(self.budgets(step)[row])


def reward(seed: int, p: float, tokens) -> float:
    """Bernoulli(p) reward of one response, fixed by (seed, its tokens)."""
    h = hashlib.blake2b(digest_size=8)
    h.update(str(seed).encode())
    h.update(b":")
    h.update(np.asarray(tokens, np.int64).tobytes())
    u = int.from_bytes(h.digest(), "little") / 2.0 ** 64
    return 1.0 if u < p else 0.0


class RewardEnv:
    """Stands in for the trainer's env on the reward side only: the prompt
    pipeline keeps the mix's own env, so the prompts are unchanged."""

    def __init__(self, seed: int, p: float):
        self.seed = seed
        self.p = p

    def reward(self, prompt, response_tokens) -> float:
        return reward(self.seed, self.p, response_tokens)
