"""Seeded traffic, read from the data files under ``bench/traffic/``.

``SftRecords``: prompt/completion records for a fine-tuning job, pure
numpy (no program import). Lengths are drawn once from the file
(``length_seed``) and grouped into rows of exactly ``row_len`` tokens, the
job's sequence length: a row takes records while they fit, and the record
that does not fit is cut to the row's end (or, where too little room is
left for a record, the row's last completion grows to fill it). A greedy
packer that fills one row at a time then packs every row whole, in any
order of rows. ``--seed`` orders the rows and the records within each row
and draws the token ids, so every seed trains on the same rows and the
same number of tokens per step.

The same seed gives the same traffic. Seeds are any non-negative integer
(``numpy.random.SeedSequence`` takes them whole).
"""
from __future__ import annotations

import numpy as np


def lognormal_length(rng, spec: dict) -> int:
    """One length, lognormal around ``median`` with ``sigma``, rounded and
    clipped to [``min``, ``max``]."""
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"])
    return int(np.clip(np.rint(x), spec["min"], spec["max"]))


def full_rows(spec: dict, row_len: int) -> list[list[tuple[int, int]]]:
    """Rows of (prompt, completion) lengths, each summing to ``row_len``,
    until they hold ``num_records`` records or more."""
    rng = np.random.default_rng(int(spec["length_seed"]))
    p_spec, c_spec = spec["prompt_len"], spec["completion_len"]
    least = int(p_spec["min"]) + int(c_spec["min"])
    rows, count = [], 0
    while count < int(spec["num_records"]):
        row, used = [], 0
        while used < row_len:
            p, c = lognormal_length(rng, p_spec), lognormal_length(rng, c_spec)
            rest = row_len - used
            if p + c > rest:
                if rest < least:                # too little room: the last
                    lp, lc = row[-1]            # completion fills the row
                    row[-1] = (lp, lc + rest)
                    break
                p = min(p, rest - int(c_spec["min"]))
                c = rest - p                    # cut to the row's end
            row.append((p, c))
            used += p + c
        rows.append(row)
        count += len(row)
    return rows


class SftRecords:
    """Prompt/completion records in rows of ``row_len`` tokens. ``arrays(i)``
    gives record ``i`` as (prompt ids, completion ids); the prompt starts
    with ``bos``."""

    def __init__(self, spec: dict, seed: int, vocab_size: int, row_len: int):
        rows = full_rows(spec, int(row_len))
        rng = np.random.default_rng(np.random.SeedSequence(seed))
        order = [rows[i] for i in rng.permutation(len(rows))]
        self.lengths = np.array([r[j] for r in order
                                 for j in rng.permutation(len(r))], np.int64)
        self.num_records = len(self.lengths)
        self.vocab_size = int(vocab_size)
        self.bos = int(spec.get("bos", 1))
        self._seed = int(seed)

    def arrays(self, index: int) -> tuple[np.ndarray, np.ndarray]:
        p, c = self.lengths[index]
        rng = np.random.default_rng([self._seed, int(index)])
        toks = rng.integers(3, self.vocab_size, int(p + c), dtype=np.int32)
        toks[0] = self.bos
        return toks[:p], toks[p:]
