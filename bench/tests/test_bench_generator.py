"""The traffic generator: a seed changes the contents, never the amount of
work."""
import numpy as np

import benchtest_support as sup
from bench import generator


def test_markov_batches_are_seeded_and_distinct():
    a = generator.markov_batches(2 ** 40, 512, 4, 16)
    b = generator.markov_batches(2 ** 40, 512, 4, 16)
    x, y = next(a), next(b)
    assert x.shape == (4, 17) and x.dtype == np.int32
    assert np.array_equal(x, y)
    z = next(a)
    rows = {r.tobytes() for r in np.concatenate([x, z])}
    assert len(rows) == 8                       # every row differs
    assert x.min() >= 0 and x.max() < 512
