"""Generator `fixed_batch`: one seeded token batch, stepped on for the
whole window.  The batch's rows and length belong to the configuration
(they are sized to the chip); the mix says only that the batch is fixed,
how many steps warm up and how many run between two reads of the loss.

Every seed gives the same amount of work (the same shapes); a seed changes
the token ids and, through the driver, the weights.
"""

from __future__ import annotations

from typing import Iterator

import numpy as np


def plan(mix: dict, seed: int, seconds: float) -> dict:
    return {"seed": int(seed), "warmup_steps": int(mix["warmup_steps"]),
            "steps_per_sync": int(mix["steps_per_sync"])}


def batches(plan: dict, rows: int, seq: int, vocab: int
            ) -> Iterator[np.ndarray]:
    """The SAME array at every step: a consumer that keeps the last batch
    on the device moves it there once."""
    tokens = np.random.default_rng(plan["seed"] % (2 ** 31 - 1)).integers(
        0, vocab, (rows, seq + 1)).astype(np.int32)
    while True:
        yield tokens
