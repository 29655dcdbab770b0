"""Closed-loop waves of requests for ``generate()``.

Parameters (a traffic file's ``params``):
  requests_per_wave   requests handed to one ``generate()`` call
  prompt_lengths      {"dist": "lognormal", "median", "sigma", "min", "max"} or
                      {"dist": "uniform", "min", "max"}
  max_new_tokens      the one budget ``generate()`` takes for the wave
  order_seed          the order the wave's requests are handed over in

Every seed gets the same lengths in the same order: the distribution's
quantiles at (i + 0.5) / n, shuffled once by the traffic file's
``order_seed``.  The seed draws the tokens (and the weights), never the work:
on the chip the order alone moved a wave's time by 5% (it decides which
chunks SplitFuse packs together; my chip run, PR 23), which is the program's
answer to one order and not noise to average over.  The same mix in another
order is another traffic file, and so another cell.  Tokens are uniform over
the vocabulary; every wave of a run has tokens of its own.
"""

import math
import statistics

import numpy as np


def quantile_lengths(spec: dict, n: int):
    qs = [(i + 0.5) / n for i in range(n)]
    if spec["dist"] == "lognormal":
        normal = statistics.NormalDist()
        raw = [spec["median"] * math.exp(spec["sigma"] * normal.inv_cdf(q)) for q in qs]
    elif spec["dist"] == "uniform":
        raw = [spec["min"] + q * (spec["max"] - spec["min"]) for q in qs]
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return [int(min(max(round(x), spec["min"]), spec["max"])) for x in raw]


class Traffic:
    def __init__(self, params: dict, seed: int, vocab_size: int):
        self.params, self.seed, self.vocab_size = params, int(seed), int(vocab_size)
        lengths = quantile_lengths(params["prompt_lengths"], params["requests_per_wave"])
        order = np.random.default_rng(params["order_seed"]).permutation(len(lengths))
        self.lengths = [lengths[i] for i in order]
        self.max_new_tokens = int(params["max_new_tokens"])

    def wave(self, index: int):
        """Prompts of wave ``index`` (0 is the warm-up's): lists of token ids."""
        rng = np.random.default_rng([self.seed, index])
        return [rng.integers(0, self.vocab_size, n).tolist() for n in self.lengths]
