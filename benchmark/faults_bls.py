"""The BLS cell's timed path broken underneath the harness, as
benchmark/faults.py breaks the other cells': each fault planted in the
PROGRAM by its name, so that a run with it planted has to come out as not
correct. (`control.py --faults` reads faults.py alone; the tests plant these.)"""

from __future__ import annotations

import contextlib

from eth_consensus_specs_tpu.ops import bls_batch


def _half_unchecked(first: bool):
    """Half of the flush left out: its aggregates accepted, no equation
    checked. The half that is checked goes in twice, so that the flush keeps
    its size and no other shape of the device program compiles."""

    def plant(real):
        def half(items, mesh=None, keys=None):
            mid = len(items) // 2
            part = slice(mid, None) if first else slice(None, mid)
            checked = real(list(items[part]) * 2, mesh=mesh, keys=keys)[: len(items[part])]
            accepted = [True] * (len(items) - len(checked))
            return accepted + checked if first else checked + accepted

        return half

    return plant


def _verdict_altered(real):
    """An answer altered where it is produced: a flush's last verdict."""

    def flipped(items, mesh=None, keys=None):
        out = real(items, mesh=mesh, keys=keys)
        return out[:-1] + [not out[-1]]

    return flipped


FAULTS = {
    "first_half_unchecked": _half_unchecked(True),
    "second_half_unchecked": _half_unchecked(False),
    "verdict_altered": _verdict_altered,
}


@contextlib.contextmanager
def planted(name: str):
    real = bls_batch.verify_many
    bls_batch.verify_many = FAULTS[name](real)
    try:
        yield
    finally:
        bls_batch.verify_many = real
