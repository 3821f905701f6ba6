"""The boundary of the device FFT (ops/fr_fft.py), tier-1 at n = 8.

Field elements cross it as ONE array of plain limbs, cut and joined by
``LimbField.ints_to_limbs_batch`` / ``limbs_to_ints_batch``; Montgomery
form begins and ends inside the one jitted program. ``tests/test_fr_fft.py``
(slow lane) covers the real sizes; here: the array split and join against
the scalar oracle, bit-equality with ``das.fft_field`` through the padded
batch, ONE executable for every direction, no compile on a second call
(ROADMAP A4's regression), and the three legs the benchmark reads.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

import jax.numpy as jnp

from eth_consensus_specs_tpu import obs
from eth_consensus_specs_tpu.crypto import das
from eth_consensus_specs_tpu.crypto.kzg import compute_roots_of_unity
from eth_consensus_specs_tpu.obs import waterfall, xprof
from eth_consensus_specs_tpu.obs.registry import Registry
from eth_consensus_specs_tpu.ops import fr_fft
from eth_consensus_specs_tpu.ops.fr_fft import BLS_MODULUS as R_MOD, FR, batch_fft_field

_rng = random.Random(20261002)
N = 8
LEGS = ("fr_fft.pack", "fr_fft.call", "fr_fft.unpack")

_EDGES = {
    "zero": 0,
    "one": 1,
    "r-1": R_MOD - 1,
    "2^30-1": (1 << 30) - 1,
    "2^30": 1 << 30,
    "2^64-1": (1 << 64) - 1,
    "2^64": 1 << 64,
    "2^255-1_mod_r": ((1 << 255) - 1) % R_MOD,
    "2r-1": 2 * R_MOD - 1,
    "every_limb_full": (1 << (30 * FR.n_limbs)) - 1,
    **{f"random{i}": _rng.randrange(R_MOD) for i in range(6)},
}


@pytest.fixture
def fresh_registry(monkeypatch):
    from eth_consensus_specs_tpu.obs import registry as registry_mod

    monkeypatch.setattr(registry_mod, "_REGISTRY", Registry())


def _rows():
    """3 rows of N: 0, r - 1 and inputs at and above r among them."""
    rows = [[_rng.randrange(R_MOD) for _ in range(N)] for _ in range(3)]
    rows[0][0], rows[0][1] = 0, R_MOD - 1
    rows[1][2], rows[1][3] = R_MOD, 2 * R_MOD + 7
    rows[2][4] = (1 << 256) + 5
    return rows


# --------------------------------------------------- the array split and join


@pytest.mark.parametrize("label", list(_EDGES))
def test_split_and_join_match_the_scalar_conversions(label):
    x = _EDGES[label]
    limbs = FR.ints_to_limbs_batch([x])
    assert limbs.dtype == np.uint64 and limbs.shape == (1, FR.n_limbs)
    assert (limbs[0] == FR.int_to_limbs(x)).all()
    assert FR.limbs_to_ints_batch(limbs) == [FR.limbs_to_int(limbs[0])] == [x]


def test_split_and_join_of_a_whole_batch_keep_the_order():
    values = list(_EDGES.values()) + [_rng.randrange(R_MOD) for _ in range(500)]
    limbs = FR.ints_to_limbs_batch(values)
    assert (limbs == np.stack([FR.int_to_limbs(v) for v in values])).all()
    assert FR.limbs_to_ints_batch(limbs.reshape(2, -1, FR.n_limbs)) == values
    assert FR.ints_to_limbs_batch([]).shape == (0, FR.n_limbs)
    assert FR.limbs_to_ints_batch(np.zeros((0, FR.n_limbs), np.uint64)) == []


@pytest.mark.parametrize("bad", [1 << (30 * FR.n_limbs), 1 << 320, -1])
def test_split_refuses_what_the_limbs_cannot_hold(bad):
    # the scalar form's `assert x == 0`, for the array form
    with pytest.raises((AssertionError, OverflowError)):
        FR.ints_to_limbs_batch([1, bad])


# ------------------------------------------------------- the transform itself


@pytest.mark.parametrize("inv", [False, True], ids=["forward", "inverse"])
def test_padded_batch_equals_the_host_fft_row_by_row(inv):
    roots = compute_roots_of_unity(N)
    rows = _rows()
    got = batch_fft_field(rows, roots, inv=inv, pad_batch=4)
    assert len(got) == 3  # the pad row is not in the result
    for out, row in zip(got, rows):
        assert out == das.fft_field([x % R_MOD for x in row], roots, inv=inv)
        assert all(type(x) is int and 0 <= x < R_MOD for x in out)


def test_unpadded_batch_and_single_vector_agree():
    roots = compute_roots_of_unity(N)
    rows = _rows()
    assert batch_fft_field(rows, roots) == [
        fr_fft.fft_field_device(row, roots) for row in rows
    ]
    coeffs = batch_fft_field(rows, roots, inv=True)
    assert batch_fft_field(coeffs, roots) == [[x % R_MOD for x in row] for row in rows]


def test_montgomery_limbs_ride_the_same_executable():
    """batch_fft_mont: Montgomery in and out (R mod r both ways), from the
    redundant range [0, 2r); forward, inverse and this wrapper share ONE
    compiled program a size, the one `kernel_ms.fr_fft` reads."""
    roots = compute_roots_of_unity(N)
    rows = [[x % R_MOD for x in row] for row in _rows()]
    mont = FR.ints_to_mont_batch(rows)
    # lift one element into [r, 2r): the same residue, the redundant form
    mont[0, 0] = FR.int_to_limbs(FR.limbs_to_int(mont[0, 0]) + R_MOD)
    out = np.asarray(fr_fft.batch_fft_mont(jnp.asarray(mont), roots))
    for got, row in zip(out, rows):
        assert FR.mont_batch_to_ints(got) == das.fft_field(row, roots)
        assert all(FR.limbs_to_int(e) < R_MOD for e in got)
    run = fr_fft._compiled_fft(N, 3)
    loaded = run._cache_size()  # this batch shape is in it by now
    batch_fft_field(rows, roots)
    batch_fft_field(rows, roots, inv=True)
    assert run._cache_size() == loaded


# ---------------------------------------------- what the benchmark reads of it


def _compile_counts() -> dict:
    hists = obs.snapshot()["histograms"]
    return {k: v["count"] for k, v in hists.items() if k.startswith("xla.compile_ms.")}


@pytest.mark.parametrize("inv", [False, True], ids=["forward", "inverse"])
def test_a_second_call_at_the_same_shape_compiles_nothing(fresh_registry, inv):
    xprof.install_compile_listener()
    roots = compute_roots_of_unity(N)
    rows = _rows()
    batch_fft_field(rows, roots, inv=inv, pad_batch=4)
    before = _compile_counts()
    assert batch_fft_field(rows, roots, inv=inv, pad_batch=4) == [
        das.fft_field([x % R_MOD for x in row], roots, inv=inv) for row in rows
    ]
    assert _compile_counts() == before
    assert before.get("xla.compile_ms.fr_fft.unpack", 0) == 0
    assert before.get("xla.compile_ms.fr_fft.pack", 0) == 0


@pytest.mark.parametrize("inv", [False, True], ids=["forward", "inverse"])
def test_each_leg_is_observed_once_a_call(fresh_registry, inv):
    roots = compute_roots_of_unity(N)
    ledger = waterfall.open_flush()
    try:
        batch_fft_field(_rows(), roots, inv=inv, pad_batch=4)
    finally:
        waterfall.close_flush()
    spans = obs.snapshot()["spans"]
    assert {name: spans[name]["count"] for name in LEGS} == dict.fromkeys(LEGS, 1)
    assert sorted(ledger) == sorted(LEGS) and all(ms >= 0 for ms in ledger.values())
