"""Training batches: ``global_batch`` rows of ``seq_len`` uniform random
tokens, a fresh batch for every step, drawn from (seed, step).  The labels are
the inputs shifted by one, with -100 (ignored) in the last place: the batch
format ``{input_ids, labels}`` is what a causal-LM loss takes."""

import numpy as np


class Traffic:
    def __init__(self, params: dict, seed: int, vocab_size: int):
        self.seed, self.vocab_size = int(seed), int(vocab_size)
        self.seq_len = int(params["seq_len"])
        self.micro_batch_per_chip = int(params["micro_batch_per_chip"])

    def ids(self, step: int, global_batch: int):
        rng = np.random.default_rng([self.seed, step])
        return rng.integers(0, self.vocab_size, (global_batch, self.seq_len), dtype=np.int32)

    def batch(self, step: int, global_batch: int):
        ids = self.ids(step, global_batch)
        labels = np.full_like(ids, -100)
        labels[:, :-1] = ids[:, 1:]
        return {"input_ids": ids, "labels": labels}
