"""The plain reference for data column sidecars (benchmark/reference/das_ref.py)
against the program's host oracle (crypto/das, the pairing) on the same cells:
what its generator makes the oracle accepts, and what is wrong either way
(a proof that opens another cell, a cell that the proof does not open) both
refuse. The reference imports nothing of the program."""

from __future__ import annotations

import numpy as np
import pytest

from benchmark.reference import das_ref as ref
from eth_consensus_specs_tpu.crypto import das, kzg

COLUMNS = (0, 1, 77, 127)


@pytest.fixture(scope="module")
def blob():
    return ref.random_blob(np.random.default_rng(2147485001))


@pytest.fixture(scope="module")
def extended(blob):
    return ref.extend_and_prove(blob)


def _oracle(index, cells, commitments, proofs) -> bool:
    return das.verify_cell_kzg_proof_batch(commitments, [index] * len(cells), cells, proofs)


def test_the_generator_cuts_the_specs_cells_and_commits_as_the_program_does(blob, extended):
    commitment, cells, proofs = extended
    assert len(cells) == len(proofs) == ref.CELLS_PER_EXT_BLOB == das.CELLS_PER_EXT_BLOB
    assert b"".join(cells[:64]) == blob  # the first half of the extension is the blob itself
    assert cells == das.compute_cells(blob)
    assert commitment == kzg.blob_to_kzg_commitment(blob)
    assert ref.ROOTS_EXT_BRP[64 * 77] == das.coset_shift_for_cell(77)


def test_the_interpolant_at_tau_is_the_specs_lagrange_polynomial_there(extended):
    _, cells, _ = extended
    evals = das.cell_to_coset_evals(cells[5])
    coeff = das._interpolate_coset_ifft(5, evals)
    assert ref.interpolant_at_tau(5, evals) == das.evaluate_polynomialcoeff(coeff, ref.TAU)


def test_the_oracle_accepts_the_generators_proofs_and_both_refuse_what_is_wrong(extended):
    commitment, cells, proofs = extended
    judge = ref.Judge()
    for col in COLUMNS:
        assert judge.verify_cell(col, commitment, cells[col], proofs[col])
        assert _oracle(col, [cells[col]], [commitment], [proofs[col]])
    # wrong either way: the next column's proof, and the next column's cell
    for col in COLUMNS:
        other = (col + 1) % 128
        for cell, proof in ((cells[col], proofs[other]), (cells[other], proofs[col])):
            assert not judge.verify_cell(col, commitment, cell, proof)
            assert not _oracle(col, [cell], [commitment], [proof])
    # a cell's verdict is kept by its bytes
    assert len(judge.cells) == 3 * len(COLUMNS)


def test_a_sidecar_is_its_structure_and_every_cell_and_the_control_checks_none(extended):
    commitment, cells, proofs = extended
    judge = ref.Judge()
    good = (5, (cells[5], cells[5]), (commitment, commitment), (proofs[5], proofs[5]))
    wrong = (5, (cells[5], cells[5]), (commitment, commitment), (proofs[5], proofs[6]))
    assert judge.verify_sidecar(good) and judge.accept_without_check(good)
    assert not judge.verify_sidecar(wrong) and judge.accept_without_check(wrong)  # UNSOUND
    over = ref.R.to_bytes(32, "big") + cells[5][32:]
    malformed = [
        (128, *good[1:]),  # index out of range
        (5, (), (), ()),  # a sidecar for zero blobs
        (5, good[1][:1], good[2], good[3]),  # lengths unequal
        (5, (cells[5][:-1], cells[5]), good[2], good[3]),  # short cell
        (5, (over, cells[5]), good[2], good[3]),  # field element not below the modulus
        (5, good[1], (b"\x01" * 48, commitment), good[3]),  # not a point
    ]
    for sidecar in malformed:
        assert not judge.verify_sidecar(sidecar) and not judge.accept_without_check(sidecar)
        index, column, commitments, proofs_ = sidecar
        with pytest.raises(AssertionError):
            assert index < 128 and len(column) > 0
            assert _oracle(index, list(column), list(commitments), list(proofs_))
