"""Plain reference of the LDA word-topic table: x0 plus every count move.

Each logged token move takes 1 from (word, old topic) and adds 1 to
(word, new topic).  Counts are integers, so the sum is exact in float64 and
in float32 (|counts| < 2**24) in any order; the parameter server's final
master must equal it bitwise.  Imports nothing of the system under test.

``dtype=bfloat16`` is the control: the same accumulation in the precision
below the table's float32, which loses counts once a cell passes 256.
"""
from __future__ import annotations

from typing import Iterable, Tuple

import numpy as np


def final_table(x0: np.ndarray,
                moves: Iterable[Tuple[np.ndarray, np.ndarray, np.ndarray]],
                dtype=np.float64) -> np.ndarray:
    total = x0.astype(dtype)
    one = np.asarray(1, dtype)
    for wd, old, new in moves:
        np.subtract.at(total, (wd, old), one)
        np.add.at(total, (wd, new), one)
    return total


def mismatches(master: np.ndarray, expected: np.ndarray) -> int:
    """Entries of the master that differ from the reference."""
    return int(np.count_nonzero(master.astype(np.float64)
                                != expected.astype(np.float64)))
