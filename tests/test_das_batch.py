"""Batched data column sidecar verification (ops/das_batch) and its serve
wiring, on the CPU at 4 columns x 2 blobs: every verdict against the host
oracle ``crypto/das.verify_cell_kzg_proof_batch`` on that sidecar alone,
ONE inverse FFT and ONE multi-MSM a flush whatever it holds, and the
routing by compiled bucket.

The sidecars come from the testing setup's public trapdoor: a cell's proof
is ``[(f(tau) - I(tau)) / (tau^64 - h^64)] G``, with ``I`` from the host's
own coset interpolation, so nothing here costs an FK20 run (a minute a
blob). The device route compiles the two programs at 8 x 64 and 8 x 2 for
XLA:CPU, a few seconds: nothing here is marked slow. The interpolation's
program (``ops/fr_fft.fold_program``, bytes in and a folded row a sidecar
out) is warmed by the ``das_msm`` key at 8 rows x 4 sidecars.
"""

from __future__ import annotations

import numpy as np
import pytest

from eth_consensus_specs_tpu import obs
from eth_consensus_specs_tpu.crypto import das, kzg, kzg_setup
from eth_consensus_specs_tpu.crypto.curve import g1_generator, g1_to_bytes
from eth_consensus_specs_tpu.crypto.fields import R
from eth_consensus_specs_tpu.obs import waterfall
from eth_consensus_specs_tpu.ops import das_batch
from eth_consensus_specs_tpu.serve import buckets
from eth_consensus_specs_tpu.serve.config import ServeConfig
from eth_consensus_specs_tpu.serve.service import VerifyService

COLUMNS = (0, 127, 5, 64)  # the domain's first and last cosets among them
WARM = [("fr_fft", 8, 64), ("das_msm", 8, 2)]  # 4 sidecars x 2 blobs: 8 cells, 8 items x 2 lanes


def _blob(rng) -> bytes:
    raw = rng.integers(0, 256, (kzg.FIELD_ELEMENTS_PER_BLOB, 32), dtype=np.uint8)
    raw[:, 0] = 0
    return raw.tobytes()


def _commit_and_open(blob: bytes, columns) -> tuple[bytes, dict]:
    """(commitment, {column: (cell, proof)}) by the trapdoor."""
    tau = kzg_setup.testing_tau()
    f_tau = kzg.evaluate_polynomial_in_evaluation_form(kzg.blob_to_polynomial(blob), tau)
    cells = das.compute_cells(blob)
    opened = {}
    for col in columns:
        interp = das._interpolate_coset_ifft(col, das.cell_to_coset_evals(cells[col]))
        z_tau = (pow(tau, 64, R) - pow(das.coset_shift_for_cell(col), 64, R)) % R
        q_tau = (f_tau - das.evaluate_polynomialcoeff(interp, tau)) * pow(z_tau, -1, R) % R
        opened[col] = (cells[col], g1_to_bytes(g1_generator().mul(q_tau)))
    return g1_to_bytes(g1_generator().mul(f_tau)), opened


@pytest.fixture(scope="module")
def blobs():
    rng = np.random.default_rng(31)
    return [_commit_and_open(_blob(rng), COLUMNS + (6,)) for _ in range(3)]


def _sidecar(blobs, col: int, rows=(0, 1)) -> tuple:
    return (
        col,
        [blobs[b][1][col][0] for b in rows],
        [blobs[b][0] for b in rows],
        [blobs[b][1][col][1] for b in rows],
    )


@pytest.fixture(scope="module")
def block(blobs):
    return [_sidecar(blobs, col) for col in COLUMNS]


def _with_proof(sidecar, row: int, proof: bytes) -> tuple:
    index, column, commitments, proofs = sidecar
    return (index, column, commitments, [*proofs[:row], proof, *proofs[row + 1 :]])


@pytest.fixture(scope="module")
def wrong_in_each_half(blobs, block):
    """A valid proof of another cell (the same blob, column 6) in one sidecar
    of each half."""
    bad = list(block)
    bad[1] = _with_proof(bad[1], 0, blobs[0][1][6][1])
    bad[3] = _with_proof(bad[3], 1, blobs[1][1][6][1])
    return bad


def _with_cell(sidecar, row: int, cell: bytes) -> tuple:
    index, column, commitments, proofs = sidecar
    return (index, [*column[:row], cell, *column[row + 1 :]], commitments, proofs)


@pytest.fixture(scope="module")
def wrong_cell_in_each_half(blobs, block):
    """Canonical bytes that are another blob's cell of the same column, in
    one sidecar of each half: what only the folded interpolation rows can
    tell from the right ones."""
    bad = list(block)
    bad[0] = _with_cell(bad[0], 1, blobs[2][1][COLUMNS[0]][0])
    bad[2] = _with_cell(bad[2], 0, blobs[2][1][COLUMNS[2]][0])
    return bad


def _oracle(sidecar) -> bool:
    index, column, commitments, proofs = sidecar
    return das.verify_cell_kzg_proof_batch(commitments, [index] * len(column), column, proofs)


@pytest.fixture(scope="module")
def warmed():
    """The two programs compiled, as a deployment's warm-up does: flushes
    of this module's bucket take the device route from here on."""
    assert buckets.precompile(WARM) == len(WARM)
    return WARM


@pytest.fixture
def route(request):
    """"device": this module's bucket warmed. "host": nothing compiled as
    far as the routing can see, for this test alone."""
    if request.param == "device":
        request.getfixturevalue("warmed")
        yield request.param
        return
    seen = buckets.seen_shapes()
    buckets.reset_for_tests()
    yield request.param
    with buckets._SEEN_LOCK:
        buckets._SEEN_SHAPES.update(seen)


FOLD_LEGS = ("das.fold", "fr_fft.pack", "fr_fft.call", "fr_fft.unpack", "das.interp_fold")
FOLD_COUNTERS = ("das.boundary_ints", "das.fold_rows_device", "das.fft_rows")


def _fold_reads() -> dict:
    """What the benchmark's per-layer metrics of the interpolation read."""
    snap = obs.snapshot()
    return {
        **{name: snap["spans"].get(name, {}).get("count", 0) for name in FOLD_LEGS},
        **{name: snap["counters"].get(name, 0) for name in FOLD_COUNTERS},
    }


def _counts() -> dict:
    snap = obs.snapshot()
    spans = {name: snap["spans"].get(name, {}).get("count", 0)
             for name in ("fr_fft.call", "g1_msm.call")}
    hists = {name: snap["histograms"].get(name, {}).get("count", 0)
             for name in ("das.msm_call_ms", "das.rlc_check_ms")}
    return {**spans, **hists, "compiles": snap["counters"].get("serve.compiles", 0)}


def _delta(before: dict) -> dict:
    return {k: v - before[k] for k, v in _counts().items()}


# ------------------------------------------------------ verdict semantics --


def test_the_trapdoor_sidecars_pass_the_host_oracle(block, wrong_in_each_half):
    assert [_oracle(s) for s in block] == [True] * 4
    assert [_oracle(s) for s in wrong_in_each_half] == [True, False, True, False]


def test_malformed_sidecars_are_false_and_exactly_those_the_oracle_refuses(block):
    index, column, commitments, proofs = block[0]
    over = R.to_bytes(32, "big") + column[0][32:]
    bad = [
        (128, column, commitments, proofs),  # index out of range
        (index, column[:1], commitments, proofs),  # lengths unequal
        (index, column, commitments, proofs[:1]),
        (index, [], [], []),  # a sidecar for zero blobs
        (index, [column[0][:-1], column[1]], commitments, proofs),  # short cell
        (index, [over, column[1]], commitments, proofs),  # field element not below the modulus
        (index, column, [b"\x01" * 48, commitments[1]], proofs),  # not a point
        (index, column, commitments, [proofs[0], b"\x80" + bytes(47)]),  # x = 0 is on no point
        (index, column, commitments, [proofs[0], b"\xc0" + b"\x01" + bytes(46)]),  # malformed infinity
    ]
    assert das_batch.prepare_columns(bad) == [None] * len(bad)
    assert [das_batch.verify_column_host(item) for item in bad] == [False] * len(bad)
    # infinity is a VALID encoding of a commitment and of a proof: parsed, then refused by the equation
    inf = kzg.G1_POINT_AT_INFINITY
    item = (index, column, commitments, [proofs[0], inf])
    assert das_batch.prepare_columns([item])[0] is not None
    assert das_batch.verify_column_host(item) is False


def test_the_c_core_and_the_python_decoding_agree(block):
    from eth_consensus_specs_tpu.crypto import native_bridge as nb

    encodings = [*block[0][2], *block[0][3], kzg.G1_POINT_AT_INFINITY, b"\x01" * 48,
                 b"\xc0" + b"\x01" + bytes(46), b"\x80" + bytes(47)]
    fast = das_batch._decode_g1(encodings)
    with nb.disabled():
        slow = das_batch._decode_g1(encodings)
    assert fast == slow and [p is None for p in fast] == [False] * 5 + [True] * 3


# ------------------------------------------------------------- the flush --


@pytest.mark.parametrize("route", ["host", "device"])
def test_verdicts_equal_the_oracles_and_a_flush_is_one_fft_and_one_msm(
        route, block, wrong_in_each_half, blobs, request):
    """All valid; one wrong proof in each half; a malformed sidecar among
    valid ones; duplicate commitments (a column's two cells of ONE blob) and
    distinct ones (sidecars of different blobs in one flush)."""
    if route == "device":
        request.getfixturevalue("warmed")
    else:
        buckets.reset_for_tests()
    malformed = (128, *block[0][1:])
    twice = _sidecar(blobs, 5, rows=(0, 0))  # the same commitment in both rows
    others = _sidecar(blobs, 64, rows=(2, 1))  # another blob's commitment in the flush
    flushes = [
        block,
        wrong_in_each_half,
        [block[0], malformed, block[2], wrong_in_each_half[3]],
        [twice, others, block[1], _with_proof(twice, 1, blobs[1][1][5][1])],
    ]
    for flush in flushes:
        before = _counts()
        got = das_batch.verify_many_columns(flush)
        assert got == [das_batch.verify_column_host(s) for s in flush]
        took = _delta(before)
        live = sum(p is not None for p in das_batch.prepare_columns(flush))
        rejects = live - sum(got)
        # ONE execution of each whatever the flush holds, the bisecting ones included
        assert took["das.msm_call_ms"] == 1
        on_device = int(route == "device")  # three live sidecars pad into the same two buckets
        assert (took["fr_fft.call"], took["g1_msm.call"]) == (on_device, on_device)
        assert took["das.rlc_check_ms"] >= 1 + 2 * rejects and took["compiles"] == 0
    assert das_batch.verify_many_columns([]) == []
    assert das_batch.verify_many_columns([malformed]) == [False]


@pytest.mark.parametrize("route", ["host", "device"], indirect=True)
def test_a_wrong_cell_in_each_half_is_refused_alone_on_either_route(
        route, block, wrong_cell_in_each_half):
    """The bisection reads the per-sidecar folded rows it already has: on
    the device route those came back folded from the one execution."""
    assert [_oracle(s) for s in wrong_cell_in_each_half] == [False, True, False, True]
    before = _counts()
    assert das_batch.verify_many_columns(wrong_cell_in_each_half) == [False, True, False, True]
    took = _delta(before)
    on_device = int(route == "device")
    assert (took["fr_fft.call"], took["g1_msm.call"], took["compiles"]) == (on_device, on_device, 0)
    assert took["das.msm_call_ms"] == 1 and took["das.rlc_check_ms"] == 7  # 1 + 2 + 4


@pytest.mark.parametrize(
    "shape",
    {
        "unequal_widths_repeated_columns": [(5, (0, 1, 2)), (5, (0,)), (127, (1, 2))],
        "padded_rows_and_segments": [(64, (2,)), (0, (0, 1))],
        "a_full_bucket": [(0, (0, 1)), (127, (1, 2)), (5, (2, 0)), (5, (1, 1))],
        "one_sidecar": [(6, (0, 1, 2, 0, 1))],
    }.items(),
    ids=lambda item: item[0],
)
def test_the_device_fold_equals_the_hosts_fold_over_the_hosts_transforms(shape, blobs, warmed):
    """`_device_interp` (bytes in, ONE execution, a folded row a sidecar
    out) against `_interp_fold` over `das.fft_field` a cell, at the bucket
    this module warmed: 8 rows x 4 sidecars."""
    _, sidecars = shape
    flush = [_sidecar(blobs, col, rows=rows) for col, rows in sidecars]
    columns = das_batch.prepare_columns(flush)
    assert None not in columns
    fold = das_batch._fold(columns)
    assert fold.cells.dtype == np.uint8 and fold.cells.shape == (len(fold.r_powers), 64, 32)
    want = das_batch._interp_fold(columns, fold, das_batch._host_coefficients(fold))
    key = buckets.das_fold_key(8, 4)
    assert buckets.is_compiled(*key)
    before = _counts()
    got = das_batch._device_interp(columns, fold, key)
    assert got == want and [len(row) for row in got] == [64] * len(flush)
    assert all(type(x) is int and 0 <= x < R for row in got for x in row)
    assert _delta(before)["compiles"] == 0


@pytest.mark.parametrize("route", ["host", "device"], indirect=True)
def test_every_leg_opens_once_a_flush_and_the_counters_follow_the_shapes(route, block):
    """The five legs the data column cell's metrics read, and the two
    counters: 64 integers a sidecar on the device route (8,192 a block of
    128) with every cell folded there (2,688 a block of 21 blobs), 64 a
    cell on the host route."""
    before = _fold_reads()
    ledger = waterfall.open_flush()
    try:
        assert das_batch.verify_many_columns(block) == [True] * 4
    finally:
        waterfall.close_flush()
    took = {k: v - before[k] for k, v in _fold_reads().items()}
    cells = sum(len(s[1]) for s in block)
    legs = dict.fromkeys(FOLD_LEGS, 1)
    if route == "host":
        legs.update({"fr_fft.pack": 0, "fr_fft.call": 0, "fr_fft.unpack": 0})
        counters = {"das.boundary_ints": 64 * cells, "das.fold_rows_device": 0}
    else:
        counters = {"das.boundary_ints": 64 * len(block), "das.fold_rows_device": cells}
    assert took == {**legs, **counters, "das.fft_rows": cells}
    assert all(name in ledger for name, n in legs.items() if n)


def test_a_second_flush_at_the_same_bucket_compiles_nothing(block, wrong_in_each_half, warmed):
    from benchmark.compile_log import CompileLog

    log = CompileLog().install()
    das_batch.verify_many_columns(block)
    mark, before = log.mark(), _counts()
    assert das_batch.verify_many_columns(wrong_in_each_half) == [True, False, True, False]
    assert CompileLog.since(mark, log.mark())["compiles"] == 0
    assert _delta(before)["compiles"] == 0


def test_the_das_msm_key_warms_the_fold_of_the_block_that_fills_its_bucket(warmed):
    """`fulu_peerdas_cells.json`'s warm-up keys stand: the `das_msm` key
    compiles the multi-MSM and, under its own key, the folding
    interpolation, and `is_compiled` says so of each."""
    assert buckets.das_fold_key(2688, 128) == ("das_fold", 4096, 128)
    assert buckets.das_fold_key(256 // 2 * 32, 256 // 2) == ("das_fold", 4096, 128)
    assert buckets.is_compiled("das_fold", 8, 4) and buckets.is_compiled("das_msm", 8, 2)
    assert ("das_fold", 8, 4) in buckets.seen_shapes()
    assert not buckets.is_compiled("das_fold", 4, 2)
    # a warm-up artifact replays the key as it was noted
    assert buckets.precompile([("das_fold", 4, 2)]) == 1 and buckets.is_compiled("das_fold", 4, 2)


def test_an_uncompiled_bucket_goes_to_the_host_and_compiles_nothing(block, warmed):
    """Two sidecars are another bucket (4 rows, 4 items x 2 lanes) than the
    one this module warmed: the flush runs on the host and no program is
    compiled for it."""
    from benchmark.compile_log import CompileLog

    log = CompileLog().install()
    mark, before = log.mark(), _counts()
    assert das_batch.verify_many_columns(block[:2]) == [True, True]
    took = _delta(before)
    assert (took["fr_fft.call"], took["g1_msm.call"], took["compiles"]) == (0, 0, 0)
    assert took["das.msm_call_ms"] == 1
    assert CompileLog.since(mark, log.mark())["compiles"] == 0
    assert not buckets.is_compiled("das_msm", 4, 2)
    assert das_batch._bucket_keys(das_batch.prepare_columns(block)) == tuple(WARM)
    # a block of 128 sidecars of 21 blobs: the cell's two buckets
    assert buckets.fr_fft_key(2688, 64) == ("fr_fft", 4096, 64)
    assert buckets.das_msm_key(256, 21) == ("das_msm", 256, 32)


# --------------------------------------------------------------- the verb --


def test_the_verb_resolves_to_the_oracles_verdict_through_a_live_service(
        block, wrong_in_each_half, warmed):
    malformed = (4, block[0][1], block[0][2][:1], block[0][3])
    with VerifyService(ServeConfig(max_batch=4, max_wait_ms=50.0, mesh_chips=1), name="das") as svc:
        assert svc.precompile(WARM) == len(WARM)
        before, was = _counts(), obs.snapshot()["counters"]
        futs = [svc.submit_column_verify(s) for s in wrong_in_each_half]
        assert [f.result(timeout=300) for f in futs] == [True, False, True, False]
        took = _delta(before)
        assert (took["fr_fft.call"], took["g1_msm.call"], took["das.msm_call_ms"]) == (1, 1, 1)
        assert took["das.rlc_check_ms"] == 7  # 1 + 2 + 4: both halves hold a reject
        # a malformed sidecar in a flush: False, and its flush's others unharmed
        futs = [svc.submit_column_verify(s) for s in [block[0], malformed, block[2], block[3]]]
        assert [f.result(timeout=300) for f in futs] == [True, False, True, True]
        counters = obs.snapshot()["counters"]
        assert counters["serve.requests.das"] - was.get("serve.requests.das", 0) == 8
        assert counters.get("serve.degraded_items", 0) == was.get("serve.degraded_items", 0)


def test_the_degraded_flush_answers_from_the_host_oracle(wrong_in_each_half):
    from eth_consensus_specs_tpu import fault

    with VerifyService(ServeConfig(max_batch=4, max_wait_ms=50.0, mesh_chips=1), name="das") as svc:
        with fault.injected("serve.dispatch:raise:times=inf"):
            futs = [svc.submit_column_verify(s) for s in wrong_in_each_half]
            assert [f.result(timeout=300) for f in futs] == [True, False, True, False]
