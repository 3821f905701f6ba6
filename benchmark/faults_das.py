"""The data column cell's timed path broken underneath the harness, as
benchmark/faults_bls.py breaks the attestation cell's: each fault planted
in the PROGRAM by its name, so that a run with it planted has to come out
as not correct. (`control.py --faults` reads faults.py alone; the tests
plant these.)"""

from __future__ import annotations

import contextlib

from eth_consensus_specs_tpu.ops import das_batch


def _half_unchecked(first: bool):
    """Half of the flush left out: its sidecars accepted, no equation
    checked. The half that is checked goes in twice, so that the flush keeps
    its size and its two buckets."""

    def plant(real):
        def half(items, parsed=None):
            mid = len(items) // 2
            part = slice(mid, None) if first else slice(None, mid)
            checked = real(list(items[part]) * 2,
                           parsed=list(parsed[part]) * 2 if parsed else None)[: len(items[part])]
            accepted = [True] * (len(items) - len(checked))
            return accepted + checked if first else checked + accepted

        return half

    return plant


def _verdict_altered(real):
    """An answer altered where it is produced: a flush's last verdict."""

    def flipped(items, parsed=None):
        out = real(items, parsed=parsed)
        return out[:-1] + [not out[-1]]

    return flipped


FAULTS = {
    "first_half_unchecked": _half_unchecked(True),
    "second_half_unchecked": _half_unchecked(False),
    "verdict_altered": _verdict_altered,
}


@contextlib.contextmanager
def planted(name: str):
    real = das_batch.verify_many_columns
    das_batch.verify_many_columns = FAULTS[name](real)
    try:
        yield
    finally:
        das_batch.verify_many_columns = real
