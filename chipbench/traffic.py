"""One general traffic generator.  A traffic mix is a data file under
``chipbench/traffic/``; this module turns it into the exact work of a run.

The seed never changes the amount of work: it draws the token ids, the
labels and which positions are masked, and the file fixes how many there
are of each.
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name):
    """The traffic file ``chipbench/traffic/<name>.json``."""
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def mlm_batches(mix, seed, n, vocab):
    """``n`` pretraining batches of a ``train_loop`` mix as numpy arrays by
    input name, drawn as ``examples/nlp/train_bert.py`` draws them, with the
    masked share exact so that no seed masks more than another."""
    rng = np.random.default_rng([int(seed), 4])
    B, S = int(mix["batch"]), int(mix["seq"])
    n_masked = int(round(mix["mask_fraction"] * B * S))
    out = []
    for _ in range(n):
        mlm = np.full((B * S,), -1, np.int64)
        pos = rng.choice(B * S, n_masked, replace=False)
        mlm[pos] = rng.integers(0, vocab, n_masked)
        out.append({
            "input_ids": rng.integers(0, vocab, (B, S)),
            "token_type_ids": rng.integers(0, 2, (B, S)),
            "attention_mask": np.ones((B, S), np.float32),
            "mlm_labels": mlm,
            "nsp_labels": rng.integers(0, 2, (B,))})
    return out
