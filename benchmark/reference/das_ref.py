"""Plain reference for Fulu data column sidecars (PeerDAS cell proofs):
field arithmetic in Python integers, hashlib, and the G1 arithmetic of
kzg_ref.py beside this file; nothing of the program.

What it decides is the Fulu spec's
``verify_data_column_sidecar(sidecar) and
verify_data_column_sidecar_kzg_proofs(sidecar)`` on one sidecar
``(index, column, kzg_commitments, kzg_proofs)`` alone
(specs/fulu/p2p-interface.md; the cell proofs:
specs/fulu/polynomial-commitments-sampling.md). Departures from the
spec's text, each with the same verdict on every well-formed input:

* The trapdoor in place of the pairing. The service under test runs on
  the repo's INSECURE testing setup, whose tau is public
  (kzg_ref.TAU). A cell's proof pi opens the commitment C on the cell's
  coset iff ``e(pi, [tau^64 - h^64]_2) == e(C - [I(tau)]_1, [1]_2)``, I
  the interpolation polynomial of the cell's 64 evaluations and h its
  coset shift; with tau in hand that is the G1 equation
  ``(tau^64 - h^64) pi + I(tau) G == C``, since G1 has prime order.
* No ``r``: the spec folds a sidecar's cells into one equation by powers
  of a Fiat-Shamir challenge, which accepts a wrong cell with
  probability ~2^-255; here every cell's own equation is checked, and a
  sidecar is accepted iff all hold.
* ``I(tau)`` by the barycentric form of Lagrange interpolation on the
  coset ``{h g^e}``: ``I(tau) = (tau^64 - h^64) / (64 h^64) * sum_e y_e
  x_e / (tau - x_e)``, the spec's ``interpolate_polynomialcoeff``
  evaluated at tau.
* The blob limit of the sidecar's epoch (``get_blob_parameters``) and
  the inclusion proof need the block header, which a request does not
  carry: the caller's.

The same trapdoor makes a blob's 128 cells and proofs for the traffic
generator: the blob extended by one 8,192-point FFT and cut in the
spec's bit-reversed coset order, each proof
``[(f(tau) - I_k(tau)) / (tau^64 - h_k^64)] G``.
"""

from __future__ import annotations

from benchmark.reference import kzg_ref as g1

R = g1.R
TAU = g1.TAU
FIELD_ELEMENTS_PER_BLOB = 4096
FIELD_ELEMENTS_PER_EXT_BLOB = 8192
FIELD_ELEMENTS_PER_CELL = 64
CELLS_PER_EXT_BLOB = 128
NUMBER_OF_COLUMNS = 128
BYTES_PER_CELL = 32 * FIELD_ELEMENTS_PER_CELL
BYTES_PER_BLOB = 32 * FIELD_ELEMENTS_PER_BLOB


def _reverse_bits(i: int, bits: int) -> int:
    return int(format(i, f"0{bits}b")[::-1], 2)


def _roots(n: int) -> list[int]:
    root = pow(7, (R - 1) // n, R)
    out, acc = [], 1
    for _ in range(n):
        out.append(acc)
        acc = acc * root % R
    return out


ROOTS_EXT = _roots(FIELD_ELEMENTS_PER_EXT_BLOB)
# the extended domain in the order the cells cut it: cell j is the 64
# evaluations at ROOTS_EXT_BRP[64 j : 64 j + 64], its coset shift the first
ROOTS_EXT_BRP = [ROOTS_EXT[_reverse_bits(i, 13)] for i in range(FIELD_ELEMENTS_PER_EXT_BLOB)]


def _inverses(values: list[int]) -> list[int]:
    """Each value's inverse mod R by ONE modular inversion."""
    prefix, acc = [], 1
    for v in values:
        prefix.append(acc)
        acc = acc * v % R
    inv = pow(acc, R - 2, R)
    out = [0] * len(values)
    for i in range(len(values) - 1, -1, -1):
        out[i] = inv * prefix[i] % R
        inv = inv * values[i] % R
    return out


# x / (tau - x) at every point of the extended domain, in the cells' order:
# the one table both the generator and the verifier interpolate with
_X_OVER_TAU_MINUS_X = [
    x * inv % R
    for x, inv in zip(ROOTS_EXT_BRP, _inverses([(TAU - x) % R for x in ROOTS_EXT_BRP]))
]
_TAU_64 = pow(TAU, FIELD_ELEMENTS_PER_CELL, R)
_INV_64 = pow(FIELD_ELEMENTS_PER_CELL, R - 2, R)


def _coset(index: int) -> tuple[int, int]:
    """(h^64, tau^64 - h^64) of cell `index`."""
    h64 = pow(ROOTS_EXT_BRP[FIELD_ELEMENTS_PER_CELL * index], FIELD_ELEMENTS_PER_CELL, R)
    return h64, (_TAU_64 - h64) % R


def interpolant_at_tau(index: int, evals: list[int]) -> int:
    """I(tau) for the polynomial of degree < 64 that takes `evals` on the
    coset of cell `index`."""
    h64, z_tau = _coset(index)
    at = FIELD_ELEMENTS_PER_CELL * index
    total = sum(y * w for y, w in zip(evals, _X_OVER_TAU_MINUS_X[at : at + FIELD_ELEMENTS_PER_CELL]))
    return total % R * z_tau % R * _INV_64 % R * pow(h64, R - 2, R) % R


# -------------------------------------------------------------- the FFT --


def _fft(values: list[int], roots: list[int]) -> list[int]:
    """o[i] = sum_j values[j] * roots[1]^(i j): iterative radix 2."""
    n = len(values)
    bits = n.bit_length() - 1
    out = [values[_reverse_bits(i, bits)] for i in range(n)]
    m = 1
    while m < n:
        stride = n // (2 * m)
        for start in range(0, n, 2 * m):
            for k in range(m):
                a = out[start + k]
                b = out[start + k + m] * roots[k * stride] % R
                out[start + k] = (a + b) % R
                out[start + k + m] = (a - b) % R
        m *= 2
    return out


def blob_coefficients(blob: bytes) -> list[int]:
    """The blob's polynomial in coefficient form: its 4,096 evaluations
    stand at the bit-reversed 4,096th roots of unity."""
    evals = g1.blob_to_polynomial(blob)
    n = FIELD_ELEMENTS_PER_BLOB
    natural = [evals[_reverse_bits(i, 12)] for i in range(n)]
    roots = ROOTS_EXT[::2]
    inverse_roots = [roots[0]] + roots[:0:-1]
    inv_n = pow(n, R - 2, R)
    return [c * inv_n % R for c in _fft(natural, inverse_roots)]


def cells_of(blob: bytes) -> list[list[int]]:
    """The 128 cells of the extended blob, 64 evaluations each."""
    padded = blob_coefficients(blob) + [0] * FIELD_ELEMENTS_PER_BLOB
    natural = _fft(padded, ROOTS_EXT)
    brp = [natural[_reverse_bits(i, 13)] for i in range(FIELD_ELEMENTS_PER_EXT_BLOB)]
    n = FIELD_ELEMENTS_PER_CELL
    return [brp[i : i + n] for i in range(0, len(brp), n)]


def cell_to_bytes(evals: list[int]) -> bytes:
    return b"".join(y.to_bytes(32, "big") for y in evals)


# ------------------------------------------------------- the generator --


def extend_and_prove(blob: bytes) -> tuple[bytes, list[bytes], list[bytes]]:
    """(commitment, the 128 cells, the 128 proofs) of a blob under the
    testing setup, by the trapdoor."""
    f_tau = g1.evaluate(g1.blob_to_polynomial(blob), TAU)
    commitment = g1.g1_compress(g1.g1_mul(g1.G1_JAC, f_tau))
    cells, proofs = [], []
    for index, evals in enumerate(cells_of(blob)):
        _, z_tau = _coset(index)
        q_tau = (f_tau - interpolant_at_tau(index, evals)) * pow(z_tau, R - 2, R) % R
        cells.append(cell_to_bytes(evals))
        proofs.append(g1.g1_compress(g1.g1_mul(g1.G1_JAC, q_tau)))
    return commitment, cells, proofs


def random_blob(rng) -> bytes:
    """31 random bytes a field element from a numpy generator, so below
    the modulus by width."""
    import numpy as np

    raw = rng.integers(0, 256, (FIELD_ELEMENTS_PER_BLOB, 32), dtype=np.uint8)
    raw[:, 0] = 0
    return raw.tobytes()


# ---------------------------------------------------------- the verdict --


def well_formed(sidecar) -> bool:
    """verify_data_column_sidecar, and the lengths and ranges that
    verify_cell_kzg_proof_batch asserts."""
    index, column, commitments, proofs = sidecar
    if not 0 <= index < NUMBER_OF_COLUMNS or len(commitments) == 0:
        return False
    if len(column) != len(commitments) or len(column) != len(proofs):
        return False
    if any(len(cell) != BYTES_PER_CELL for cell in column):
        return False
    return all(len(p) == 48 for p in (*commitments, *proofs))


class Judge:
    """Verdicts with their memory: a cell's verdict is kept by its bytes
    (index, commitment, cell, proof), a commitment's point by its bytes,
    since a window's blocks draw their blobs from one pool."""

    def __init__(self):
        self.cells: dict[tuple, bool] = {}
        self.points: dict[bytes, object] = {}

    def _point(self, data: bytes):
        if data not in self.points:
            try:
                self.points[data] = (g1.g1_decompress(data),)
            except ValueError:
                self.points[data] = None
        return self.points[data]

    def verify_cell(self, index: int, commitment: bytes, cell: bytes, proof: bytes) -> bool:
        key = (index, commitment, cell, proof)
        if key not in self.cells:
            self.cells[key] = self._verify_cell(*key)
        return self.cells[key]

    def _verify_cell(self, index: int, commitment: bytes, cell: bytes, proof: bytes) -> bool:
        c_pt, q_pt = self._point(commitment), self._point(proof)
        if c_pt is None or q_pt is None:
            return False
        evals = [int.from_bytes(cell[i : i + 32], "big") for i in range(0, BYTES_PER_CELL, 32)]
        if max(evals) >= R:
            return False
        _, z_tau = _coset(index)
        lhs = g1._add(
            g1.g1_mul(q_pt[0], z_tau),
            g1.g1_mul(g1.G1_JAC, interpolant_at_tau(index, evals)),
        )
        return g1.g1_equal(lhs, c_pt[0])

    def verify_sidecar(self, sidecar) -> bool:
        """The sidecar's verdict, alone: its structure, then every cell."""
        if not well_formed(sidecar):
            return False
        index, column, commitments, proofs = sidecar
        return all([
            self.verify_cell(index, bytes(c), bytes(cell), bytes(p))
            for c, cell, p in zip(commitments, column, proofs)
        ])

    def accept_without_check(self, sidecar) -> bool:
        """The control: the sidecar is parsed, its points are checked to be
        points and its cells field elements, and no equation is checked
        (UNSOUND). It breaks the guarantee that an accepted sidecar's
        proofs open its commitments on its column's cosets."""
        if not well_formed(sidecar):
            return False
        _, column, commitments, proofs = sidecar
        if any(self._point(bytes(p)) is None for p in (*commitments, *proofs)):
            return False
        return all(
            int.from_bytes(cell[i : i + 32], "big") < R
            for cell in column for i in range(0, BYTES_PER_CELL, 32)
        )
