"""The one traffic generator: a traffic file's parameters in, requests out.

Sizes (output lengths, the ages of the rollouts in flight) come from the
file's own ``sizes_seed``, so every run seed gives the same set of sizes and
so the same work; the run seed only orders them and draws the token ids.

Output lengths follow the paper's math-reasoning profile as the repository
states it (``benchmarks/common.py``, ``LengthDistribution``): lognormal with
a given mean and coefficient of variation, clipped to ``[min, max]``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Iterator, List

import numpy as np

DRAWS = 4096          # candidate rollouts the steady state is drawn from


def lognormal_lengths(spec: Dict, rng: np.random.Generator, n: int) -> np.ndarray:
    sigma2 = math.log(1.0 + spec["cv"] ** 2)
    mu = math.log(spec["mean"]) - sigma2 / 2.0
    out = rng.lognormal(mu, math.sqrt(sigma2), size=n)
    return np.clip(out, spec["min"], spec["max"]).astype(np.int64)


def pages_for(tokens: int, page: int) -> int:
    return -(-tokens // page)


@dataclass
class Request:
    group: int
    prompt: List[int]          # what the engine prefills
    max_new: int               # tokens it still has to produce


class RolloutPlan:
    """A rollout replica in steady state, from a traffic file.

    In flight: rollouts whose lengths are drawn length-biased (a slot is
    more often busy with a long rollout) and whose ages are uniform over
    their lengths; each carries its prompt plus the completion it has
    produced so far, which the engine prefills again (recompute on
    interrupt).  They fill the pool as a deployment fills it: the largest
    number of slots whose rollouts, taken at evenly spaced quantiles of the
    steady-state context, fit with one page of headroom each into
    ``num_pages`` less the null page.  Behind them a
    closed backlog of GRPO groups of ``group_size`` siblings, one fresh
    prompt per group, one drawn output length per sibling.
    """

    def __init__(self, traffic: Dict, vocab: int, seed: int):
        eng = traffic["engine"]
        out = traffic["output"]
        self.page = int(eng["page_size"])
        self.num_pages = int(eng["num_pages"])
        self.prefill_chunk = int(eng["prefill_chunk"])
        self.group_size = int(traffic["group_size"])
        self.prompt_len = int(traffic["prompt_len"])
        self.max_out = int(out["max"])
        self.max_len = self.prompt_len + self.max_out
        self.max_pages_per_seq = pages_for(self.max_len, self.page)
        self.queue_depth = int(traffic["queue_depth"])
        self.temperature = float(traffic["sampling"]["temperature"])
        self.top_p = float(traffic["sampling"]["top_p"])
        self.vocab = int(vocab)

        sizes = np.random.default_rng(int(traffic["sizes_seed"]))
        lengths = lognormal_lengths(out, sizes, DRAWS)
        biased = sizes.choice(lengths, size=DRAWS, p=lengths / lengths.sum())
        ages = np.floor(sizes.random(DRAWS) * biased).astype(np.int64)
        by_ctx = sorted(zip(ages.tolist(), biased.tolist()))
        inflight = []
        for n in range(1, self.num_pages):
            pick = [by_ctx[int((j + 0.5) * DRAWS / n)] for j in range(n)]
            need = sum(pages_for(self.prompt_len + a + 1, self.page) + 1
                       for a, _ in pick)
            if need > self.num_pages - 1:
                break
            inflight = pick
        if not inflight:
            raise ValueError("num_pages holds no rollout of this traffic")
        self.inflight_sizes = inflight
        self.max_slots = len(inflight)
        self.backlog_sizes = lognormal_lengths(
            out, sizes, int(traffic["backlog_groups"]) * self.group_size
        ).reshape(-1, self.group_size)

        self._rng = np.random.default_rng(seed)
        self._inflight_order = self._rng.permutation(self.max_slots)
        self._backlog_order = self._rng.permutation(len(self.backlog_sizes))

    def mean_steady_context(self) -> float:
        return float(np.mean([self.prompt_len + a for a, _ in self.inflight_sizes]))

    def _tokens(self, n: int) -> List[int]:
        return self._rng.integers(0, self.vocab, size=n).tolist()

    def inflight(self) -> List[Request]:
        """The rollouts that hold the slots when the window opens, in the
        seed's order; siblings of one group share the prompt."""
        reqs, prompt = [], []
        for i, j in enumerate(self._inflight_order):
            if i % self.group_size == 0:
                prompt = self._tokens(self.prompt_len)
            age, length = self.inflight_sizes[j]
            reqs.append(Request(group=i // self.group_size,
                                prompt=prompt + self._tokens(age),
                                max_new=length - age))
        return reqs

    def backlog(self) -> Iterator[List[Request]]:
        """Endless GRPO groups for the closed backlog."""
        first = -(-self.max_slots // self.group_size)
        for k in range(1 << 62):
            sizes = self.backlog_sizes[
                self._backlog_order[k % len(self._backlog_order)]]
            prompt = self._tokens(self.prompt_len)
            yield [Request(group=first + k, prompt=prompt, max_new=int(n))
                   for n in sizes]
