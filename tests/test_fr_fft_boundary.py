"""The boundary of the device FFT (ops/fr_fft.py), tier-1 at n = 8.

Field elements cross it as ONE array of plain limbs, cut and joined by
``LimbField.ints_to_limbs_batch`` / ``limbs_to_ints_batch``; Montgomery
form begins and ends inside the one jitted program. ``tests/test_fr_fft.py``
(slow lane) covers the real sizes; here: the array split and join against
the scalar oracle, bit-equality with ``das.fft_field`` through the padded
batch, ONE executable for every direction, no compile on a second call
(ROADMAP A4's regression), and the three legs the benchmark reads.

The second boundary over the same program, a data column flush's
(``batch_ifft_folded``): bytes in, limbs cut from 32-bit words by the ONE
cutter, rows weighted and added a segment BEFORE the stages, one scaled
row a segment back. Here at n = 8 with a made-up scale table;
``tests/test_das_batch.py`` holds it to the sidecars' verdicts.
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


# ------------------------------------------ the data column flush's boundary

FOLD_BUCKET = (8, 4)  # rows, segments
WIDTHS = {
    "equal": (2, 2, 2),
    "unequal": (3, 1, 2),  # two padded rows, one padded segment
    "one_wide": (7,),
    "full": (2, 2, 2, 2),  # no padding at all
    "singles": (1, 1, 1),
}


def _cell_ints(widths):
    """One row of N canonical elements a cell, 0 and r - 1 among them."""
    rows = [[_rng.randrange(R_MOD) for _ in range(N)] for _ in range(sum(widths))]
    rows[0][0], rows[0][N - 1], rows[-1][3] = 0, R_MOD - 1, R_MOD - 1
    return rows


def _as_cells(rows) -> np.ndarray:
    raw = b"".join(x.to_bytes(32, "big") for row in rows for x in row)
    return np.frombuffer(raw, np.uint8).reshape(len(rows), N, 32)


@pytest.fixture(scope="module")
def scale_table():
    """Five rows of N plain scale factors, the transform's 1/N in them, as
    the caller of ``batch_ifft_folded`` keeps them on the device."""
    ints = [[_rng.randrange(1, R_MOD) for _ in range(N)] for _ in range(5)]
    n_inv = pow(N, -1, R_MOD)
    flat = [s * n_inv % R_MOD for row in ints for s in row]
    return ints, jnp.asarray(FR.ints_to_limbs_batch(flat).reshape(5, N, FR.n_limbs))


def _fold_inputs(widths, scale_of):
    """(cell integers, weights, and the padded arrays a flush of these
    widths hands the boundary)."""
    rows_pad, segs_pad = FOLD_BUCKET
    ints = _cell_ints(widths)
    weights = [_rng.randrange(R_MOD) for _ in ints]
    weights[-1] = R_MOD - 1
    limbs = np.zeros((rows_pad, FR.n_limbs), np.uint64)
    limbs[: len(ints)] = FR.ints_to_limbs_batch(weights)
    segments = np.full(rows_pad, len(widths) - 1, np.int32)
    segments[: len(ints)] = np.repeat(np.arange(len(widths), dtype=np.int32), widths)
    scale_rows = np.zeros(segs_pad, np.int32)
    scale_rows[: len(widths)] = scale_of
    return ints, weights, limbs, segments, scale_rows


def _host_fold(ints, weights, widths, scales):
    """Per segment ``scale * IFFT(row)`` weighted and added, by the host's
    transform a row: the rows arrive in bit-reversed order."""
    roots = compute_roots_of_unity(N)
    brp = fr_fft._bit_reversal_indices(N)
    coeffs = [das.fft_field([row[i] for i in brp], roots, inv=True) for row in ints]
    out, at = [], 0
    for width, scale in zip(widths, scales):
        acc = [0] * N
        for row, w in zip(coeffs[at : at + width], weights[at : at + width]):
            acc = [(a + w * c) % R_MOD for a, c in zip(acc, row)]
        out.append([a * s % R_MOD for a, s in zip(acc, scale)])
        at += width
    return out


@pytest.mark.parametrize("where", ["numpy", "device"])
def test_the_cutter_makes_of_a_cells_bytes_the_limbs_of_its_integers(where):
    ints = _cell_ints((3, 2)) + [[0] * N, [R_MOD - 1] * N]
    words = fr_fft.cells_to_words(_as_cells(ints), pad_batch=8)
    assert words.dtype == np.dtype("<u4") and words.shape == (8, 8 * N)
    assert not words[len(ints) :].any()  # the rows up to the bucket
    words = words.reshape(8, N, 8)
    limbs = np.asarray(FR.words_to_limbs(jnp.asarray(words) if where == "device" else words))
    assert limbs.dtype == np.uint64 and limbs.shape == (8, N, FR.n_limbs)
    for got_row, row in zip(limbs, ints):
        for got, x in zip(got_row, row):  # x is int.from_bytes of the element's 32 bytes
            assert (got == FR.int_to_limbs(x)).all()


@pytest.mark.parametrize("bits", [32, 64])
def test_the_cutter_takes_words_of_either_width(bits):
    values = list(_EDGES.values())
    size = FR.n_words * 8
    raw = b"".join(v.to_bytes(size, "little") for v in values)
    words = np.frombuffer(raw, f"<u{bits // 8}").reshape(len(values), -1)
    assert (FR.words_to_limbs(words) == np.stack([FR.int_to_limbs(v) for v in values])).all()


@pytest.mark.parametrize("shape", list(WIDTHS))
def test_the_folded_transform_equals_the_hosts_row_by_row(shape, scale_table):
    widths = WIDTHS[shape]
    scale_ints, scale = scale_table
    scale_of = [(3 * j + 1) % 5 for j in range(len(widths))]
    if len(widths) > 2:
        scale_of[2] = scale_of[0]  # a repeated scale row
    ints, weights, limbs, segments, scale_rows = _fold_inputs(widths, scale_of)
    got = fr_fft.batch_ifft_folded(
        _as_cells(ints), compute_roots_of_unity(N), limbs, segments, scale, scale_rows,
        live=len(widths),
    )
    assert got == _host_fold(ints, weights, widths, [scale_ints[i] for i in scale_of])
    assert all(type(x) is int and 0 <= x < R_MOD for row in got for x in row)


def test_a_row_bucket_whose_sum_could_pass_the_limbs_is_refused():
    """B * 2r < R is what keeps a segment's unreduced sum inside the L
    limbs: stated where the program is traced, whatever the flush holds."""
    import jax

    assert 16384 * 2 * R_MOD < FR.r_int <= 32768 * 2 * R_MOD

    def shapes(rows):
        u64 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.uint64)  # noqa: E731
        return (
            jax.ShapeDtypeStruct((rows, 8 * N), jnp.uint32), u64(rows, FR.n_limbs),
            jax.ShapeDtypeStruct((rows,), jnp.int32), u64(FR.n_limbs), u64(5, N, FR.n_limbs),
            jax.ShapeDtypeStruct((4,), jnp.int32), *(u64(1 << i, FR.n_limbs) for i in range(3)),
        )

    run = fr_fft._compiled_fold(N, 3)
    assert jax.eval_shape(run, *shapes(16384)).shape == (4, N, FR.n_limbs)
    with pytest.raises(AssertionError):
        jax.eval_shape(run, *shapes(32768))


def test_a_second_flush_at_the_same_bucket_compiles_nothing(fresh_registry, scale_table):
    xprof.install_compile_listener()
    _, scale = scale_table
    roots = compute_roots_of_unity(N)
    for widths in ((2, 2, 2), (3, 1, 2)):  # the first warms, whatever ran before
        ints, _, limbs, segments, scale_rows = _fold_inputs(widths, [0, 1, 2])
        before = _compile_counts()
        fr_fft.batch_ifft_folded(_as_cells(ints), roots, limbs, segments, scale, scale_rows, live=3)
    assert _compile_counts() == before
    # the blob boundary's program is another executable and stays what it was
    assert fr_fft._compiled_fold(N, 3) is not fr_fft._compiled_fft(N, 3)


def test_each_leg_of_the_folded_boundary_is_observed_once_a_call(fresh_registry, scale_table):
    _, scale = scale_table
    ints, _, limbs, segments, scale_rows = _fold_inputs((3, 1, 2), [4, 4, 0])
    ledger = waterfall.open_flush()
    try:
        fr_fft.batch_ifft_folded(
            _as_cells(ints), compute_roots_of_unity(N), limbs, segments, scale, scale_rows, live=3)
    finally:
        waterfall.close_flush()
    spans = obs.snapshot()["spans"]
    assert {name: spans[name]["count"] for name in LEGS} == dict.fromkeys(LEGS, 1)
    assert sorted(ledger) == sorted(LEGS) and all(ms >= 0 for ms in ledger.values())
