"""The one traffic generator: a mix file's parameters and a seed make an
open-loop schedule of requests.

Every seed gets the same prompt lengths, output lengths and gaps between
arrivals, in the same order, so the work of a run does not depend on
its seed: the sizes are the mix's distributions read at evenly spaced
quantiles, put in one fixed order (``ORDER_SEED``). Each mix therefore
has one realization of its schedule; the run's seed draws the prompt
tokens (and, elsewhere, the weights). A mix file
(``bench/traffic/<mix>.json``) holds:

  arrival  {"kind": "poisson"} or {"kind": "gamma", "cv": 2.0}
  rate_per_s             the offered rate; a run sends rate x seconds
  prompt, output         {"median", "sigma", "min", "max"}: lognormal,
                         clipped
  reply_cap_s            how long after the window a reply is awaited
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

# the server's receive buffer is 4096 bytes of int32 words: [budget, tag,
# prompt...] leaves 1022 prompt tokens
MAX_PROMPT_TOKENS = 1022
# draws the one order of every mix's sizes and gaps
ORDER_SEED = 1


@dataclass
class Schedule:
    due_s: np.ndarray          # [N] seconds after the window opens
    prompt_len: np.ndarray     # [N]
    budget: np.ndarray         # [N] tokens to generate
    prompts: list              # N int32 arrays

    def __len__(self) -> int:
        return len(self.due_s)

    def payload(self, i: int) -> bytes:
        """Request ``i`` on the wire: int32 ``[budget, tag, prompt...]``,
        the tag being ``i``."""
        head = np.asarray([self.budget[i], i], np.int32)
        return np.concatenate([head, self.prompts[i]]).tobytes()

    def save(self, path: Path) -> None:
        np.savez(path, due_s=self.due_s, prompt_len=self.prompt_len,
                 budget=self.budget,
                 tokens=np.concatenate(self.prompts).astype(np.int32))

    @classmethod
    def load(cls, path: Path) -> "Schedule":
        z = np.load(path)
        cuts = np.cumsum(z["prompt_len"])[:-1]
        return cls(z["due_s"], z["prompt_len"], z["budget"],
                   list(np.split(z["tokens"], cuts)))


def load_mix(path) -> dict:
    return json.loads(Path(path).read_text())


def _quantile_points(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_sizes(spec: dict, n: int) -> np.ndarray:
    """``n`` sizes at evenly spaced quantiles of a lognormal with the
    given median and sigma, rounded and clipped to ``[min, max]``."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf(p) for p in _quantile_points(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def unit_gaps(arrival: dict, n: int) -> np.ndarray:
    """``n`` gaps with mean 1 at evenly spaced quantiles of the arrival
    process: exponential for Poisson arrivals, gamma with the stated
    coefficient of variation for bursty ones."""
    p = _quantile_points(n)
    kind = arrival["kind"]
    if kind == "poisson":
        g = -np.log1p(-p)
    elif kind == "gamma":
        from scipy.special import gammaincinv
        shape = 1.0 / float(arrival["cv"]) ** 2
        g = gammaincinv(shape, p) / shape
    else:
        raise ValueError(f"unknown arrival kind {kind!r}")
    return g / g.mean()


def make_schedule(mix: dict, seed: int, seconds: float, vocab: int, *,
                  rate_per_s: float | None = None) -> Schedule:
    """The schedule of one run: ``rate x seconds`` requests due inside
    ``[0, seconds)``. ``rate_per_s`` overrides the mix's rate (a knee
    sweep)."""
    rate = float(rate_per_s if rate_per_s is not None else mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    if mix["prompt"]["max"] > MAX_PROMPT_TOKENS:
        raise ValueError(f"prompts of up to {mix['prompt']['max']} tokens "
                         f"exceed the wire's {MAX_PROMPT_TOKENS}")
    order = np.random.default_rng([ORDER_SEED, 0x0DE4])
    gaps = unit_gaps(mix["arrival"], n)[order.permutation(n)]
    gaps *= seconds / gaps.sum()
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    plen = lognormal_sizes(mix["prompt"], n)[order.permutation(n)]
    budget = lognormal_sizes(mix["output"], n)[order.permutation(n)]
    rng = np.random.default_rng([int(seed) % 2**64, 0x7AFF1C])
    prompts = [rng.integers(0, vocab, int(k), dtype=np.int32) for k in plen]
    return Schedule(due, plen, budget, prompts)


def longest_gap_s(sched: Schedule) -> float:
    """The longest wait between two arrivals."""
    return float(np.diff(sched.due_s).max()) if len(sched) > 1 else 0.0
