"""The one traffic generator: every mix is a file of parameters.

All draws come from ``numpy.random.default_rng([seed, stream, index])``
with a fixed stream number per purpose, so the same ``--seed`` gives the
same data bit for bit, whatever else a run draws. A mix that needs a new
kind of draw (arrival times, length distributions, sessions) adds its
stream number and its function here, in the PR that adds its driver. No
jax.
"""

from __future__ import annotations

import numpy as np

# One substream per purpose.
S_ROWS = 5


def rng(seed: int, stream: int, index: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream), int(index)])


def token_rows(ids, seed: int, seq_len: int, vocab: int) -> np.ndarray:
    """Seeded token rows [n, seq_len + 1], one generator per row id, so
    the data does not depend on how blocks were cut."""
    return np.stack([rng(seed, S_ROWS, int(i)).integers(
        0, vocab, size=seq_len + 1, dtype=np.int32) for i in ids])
