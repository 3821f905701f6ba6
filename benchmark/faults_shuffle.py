"""The committee cell's timed path broken underneath the harness, as
benchmark/faults_das.py breaks the data column cell's: each fault planted
in the PROGRAM by its name, so that a run with it planted has to come out
as not correct. The first `warm` calls go through untouched (set-up sends
the pool once and has to succeed); every answer after them carries the
fault. (`control.py --faults` reads faults.py alone; the tests plant
these.)"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from eth_consensus_specs_tpu.ops import shuffle
from eth_consensus_specs_tpu.serve import buckets

HOLD_S = 1.0  # how long `never_answers` keeps an answer: past the tests' timeout


def _entries_swapped(real, active, seed, rounds):
    """An answer altered where it is produced: its first two entries swapped."""
    out = np.array(real(active, seed, rounds))
    out[[0, 1]] = out[[1, 0]]
    return out


def _tail_unshuffled(real, active, seed, rounds):
    """The positions past the last whole chunk of 256 left as they came: a
    program that shuffles whole chunks alone."""
    out = np.array(real(active, seed, rounds))
    whole = len(active) // 256 * 256
    out[whole:] = active[whole:]
    return out


def _count_as_bucket(real, active, seed, rounds):
    """The count taken as its lane bucket: the list padded to the bucket is
    shuffled whole and cut to the count."""
    n = len(active)
    padded = np.concatenate([active, np.arange(n, buckets.shuffle_key(n)[1], dtype=active.dtype)])
    return np.array(real(padded, seed, rounds))[:n]


def _never_answers(real, active, seed, rounds):
    """An answer that never comes: kept past any client's patience."""
    time.sleep(HOLD_S)
    return real(active, seed, rounds)


FAULTS = {
    "entries_swapped": _entries_swapped,
    "tail_unshuffled": _tail_unshuffled,
    "count_as_bucket": _count_as_bucket,
    "never_answers": _never_answers,
}


@contextlib.contextmanager
def planted(name: str, warm: int = 0):
    real, calls = shuffle.shuffled_indices, [0]

    def broken(active, seed, rounds):
        calls[0] += 1
        if calls[0] <= warm:
            return real(active, seed, rounds)
        return FAULTS[name](real, active, seed, rounds)

    shuffle.shuffled_indices = broken
    try:
        yield
    finally:
        shuffle.shuffled_indices = real
