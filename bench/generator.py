"""The one generator every traffic file feeds: training batches made from
``--seed`` and the file's parameters. A seed changes the tokens, never the
amount of work."""
from __future__ import annotations

from typing import Iterator

import numpy as np


def markov_batches(seed: int, vocab: int, batch: int, seq: int,
                   fan_out: int = 8) -> Iterator[np.ndarray]:
    """Endless [batch, seq + 1] int32 batches of an order-1 Markov chain
    over the vocabulary with ``fan_out`` successors per token, so a model
    can learn it (the program's ``data.synthetic.token_lm_stream``, copied
    and made to draw a whole batch per position)."""
    rng = np.random.default_rng([int(seed), 7])
    nexts = rng.integers(0, vocab, (vocab, fan_out)).astype(np.int32)
    while True:
        out = np.empty((batch, seq + 1), np.int32)
        out[:, 0] = rng.integers(0, vocab, batch)
        picks = rng.integers(0, fan_out, (batch, seq))
        for t in range(1, seq + 1):
            out[:, t] = nexts[out[:, t - 1], picks[:, t - 1]]
        yield out
