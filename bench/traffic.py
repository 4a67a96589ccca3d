"""The one generator of every traffic mix: ``traffic/<mix>.json`` holds
only parameters, read here.

Kinds:

* ``train``: at every step a block of ``global_batch`` rows of
  ``seq_len + 1`` token ids from the mix's ``stream``, drawn by the run's
  seed and the step's number.  A row's first ``seq_len`` ids are the
  inputs and its last ``seq_len`` the targets; the runner and the
  reference each take them apart from the same block.  Every seed gives
  the same work: the same shapes, other ids.
"""
from __future__ import annotations

import numpy as np


def train_block(mix: dict, vocab: int, seed: int, step: int) -> np.ndarray:
    """The ``(global_batch, seq_len + 1)`` int32 token ids of ``step``.

    A row starts at a random id and goes on by ``t' = (mult * t + add) %
    vocab``, each next id replaced by a random one with probability
    ``noise``: a stream with learnable structure, so that a model's loss
    can fall on it.
    """
    st = mix["stream"]
    rng = np.random.default_rng(np.random.SeedSequence([seed, step, 0]))
    B, S = mix["global_batch"], mix["seq_len"]
    toks = np.empty((B, S + 1), dtype=np.int32)
    toks[:, 0] = rng.integers(0, vocab, B)
    noise = rng.random((B, S)) < st["noise"]
    rand = rng.integers(0, vocab, (B, S))
    for t in range(S):
        nxt = (st["mult"] * toks[:, t] + st["add"]) % vocab
        toks[:, t + 1] = np.where(noise[:, t], rand[:, t], nxt)
    return toks
