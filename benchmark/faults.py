"""The timed path broken underneath the harness: each fault a cell can have,
planted in the PROGRAM by its name, so that the tests (CPU, tiny size) and
`control.py` (the chip, the cell's own size) plant the same thing. A run with
a fault planted has to come out as not correct. The benchmark's own runs
never import this."""

from __future__ import annotations

import contextlib
import importlib

import numpy as np


def _root_altered(real):
    """An answer altered where it is produced: one bit of every root."""
    return lambda *a: np.asarray(real(*a)) ^ np.uint32(1)


def _root_stale(real):
    """The state left unchanged: the answer to the request before."""
    last = []

    def stale(*a):
        last.append(real(*a))
        return last[-2] if len(last) > 1 else last[-1]

    return stale


def _half_unchecked(first: bool):
    """Half of the flush left out: its sidecars accepted, no proof opened.
    The half that is checked goes in twice, so that the flush keeps its size
    and no narrower shape of a limb program compiles (minutes on the chip)."""

    def plant(real):
        def half(items, mesh=None, parsed=None):
            mid = len(items) // 2
            part = slice(mid, None) if first else slice(None, mid)
            checked = real(list(items[part]) * 2, mesh=mesh,
                           parsed=list(parsed[part]) * 2 if parsed else None)[: len(items[part])]
            accepted = [True] * (len(items) - len(checked))
            return accepted + checked if first else checked + accepted

        return half

    return plant


def _verdict_altered(real):
    """An answer altered where it is produced: a flush's last verdict."""

    def flipped(items, mesh=None, parsed=None):
        out = real(items, mesh=mesh, parsed=parsed)
        return out[:-1] + [not out[-1]]

    return flipped


# name -> (module of the program, attribute, what takes its place)
FAULTS = {
    "root_altered": ("ops.state_root", "post_epoch_state_root", _root_altered),
    "root_stale": ("ops.state_root", "post_epoch_state_root", _root_stale),
    "first_half_unchecked": ("ops.kzg_batch", "verify_many_blobs", _half_unchecked(True)),
    "second_half_unchecked": ("ops.kzg_batch", "verify_many_blobs", _half_unchecked(False)),
    "verdict_altered": ("ops.kzg_batch", "verify_many_blobs", _verdict_altered),
}


@contextlib.contextmanager
def planted(name: str):
    module, attr, plant = FAULTS[name]
    target = importlib.import_module(f"eth_consensus_specs_tpu.{module}")
    real = getattr(target, attr)
    setattr(target, attr, plant(real))
    try:
        yield
    finally:
        setattr(target, attr, real)
