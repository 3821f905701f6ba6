"""The registry's public keys, decoded and KeyValidated ONCE and kept.

A block's attestations name 65,536 validators of a registry of 2^20, half
of them new each block; decompressing a key (a square root and a subgroup
check, ~0.2 ms in the C core) every time it is met costs seconds a block.
A deployment therefore hands its registry over once. The table keeps, by
registry index:

  * on the host, the affine points in the C core's own form (96 bytes a
    key: 100 MB at 2^20), for committee sums through the core;
  * on the device, their Montgomery limbs (2 x 13 x u64 a key: 218 MB at
    2^20), made at the first device sum, for ``g1_msm.sum_indexed_kernel``:
    a flush then sends indices and packs no point.

A request names its signers by registry index, or by their 48 bytes, which
one dictionary lookup a key resolves to the index.
"""

from __future__ import annotations

import numpy as np

from eth_consensus_specs_tpu import obs
from eth_consensus_specs_tpu.crypto import native_bridge as nb
from eth_consensus_specs_tpu.crypto.curve import B1, Point, g1_from_bytes
from eth_consensus_specs_tpu.crypto.fields import Fq
from eth_consensus_specs_tpu.crypto.fields import P as P_INT
from eth_consensus_specs_tpu.obs import waterfall

from .field_limbs import LIMB_BITS, MASK, N_LIMBS, R_INT


def _validated_affine(compressed: bytes) -> np.ndarray:
    """uint8[N, 96] affine points of the N 48-byte keys in `compressed`;
    ValueError on the first key that fails KeyValidate (malformed, off the
    curve, outside the subgroup, or infinity)."""
    count = len(compressed) // 48
    if nb.enabled():
        raw, bad = nb.g1_key_validate_many(compressed)
    else:  # the pure-Python oracle, a key at a time
        raw, bad = bytearray(), count
        for at in range(count):
            try:
                p = g1_from_bytes(compressed[48 * at : 48 * at + 48])
            except ValueError:
                p = None
            if p is None or p.is_infinity():
                bad = at
                break
            raw += p.x.n.to_bytes(48, "big") + p.y.n.to_bytes(48, "big")
    if bad < count:
        raise ValueError(f"public key {bad} of the registry fails KeyValidate")
    return np.frombuffer(bytes(raw), np.uint8).reshape(count, 96)


def _mont_limbs(coordinates: np.ndarray) -> np.ndarray:
    """u64[N, 13] Montgomery limbs of uint8[N, 48] big-endian field
    elements: the multiplication by R a key in Python integers, the split
    into 30-bit limbs in numpy."""
    words = np.frombuffer(
        b"".join(
            (int.from_bytes(row, "big") * R_INT % P_INT).to_bytes(56, "little")
            for row in _rows(coordinates)
        ),
        np.uint64,
    ).reshape(len(coordinates), 7)
    limbs = np.empty((len(coordinates), N_LIMBS), np.uint64)
    for i in range(N_LIMBS):
        word, shift = divmod(LIMB_BITS * i, 64)
        value = words[:, word] >> np.uint64(shift)
        if shift > 64 - LIMB_BITS:
            value = value | (words[:, word + 1] << np.uint64(64 - shift))
        limbs[:, i] = value & np.uint64(MASK)
    return limbs


def _rows(coordinates: np.ndarray):
    flat = coordinates.tobytes()
    return (flat[at : at + 48] for at in range(0, len(flat), 48))


class KeyTable:
    """Everything by registry index, in containers the garbage collector
    does not walk (arrays, and a dictionary of bytes to int): a full
    collection in the middle of a flush otherwise visits 2^20 entries of
    each."""

    def __init__(self, pubkeys: list):
        if any(len(pk) != 48 for pk in pubkeys):
            raise ValueError("a public key is 48 bytes")
        blob = b"".join(pubkeys)
        self.compressed = np.frombuffer(blob, np.uint8).reshape(len(pubkeys), 48)
        with waterfall.leg("key_table.validate", keys=len(pubkeys)):
            self.affine = _validated_affine(blob)
        self.index_of = {blob[at : at + 48]: at // 48 for at in range(0, len(blob), 48)}
        self._limbs: dict = {}  # by mesh (None: the default device)

    def __len__(self) -> int:
        return len(self.affine)

    def resolve(self, signers) -> np.ndarray | None:
        """int32 registry indices of a request's signers, given as indices
        or as 48-byte keys; None where one of them is not in the table."""
        if isinstance(signers, np.ndarray):
            ok = signers.size and 0 <= int(signers.min()) and int(signers.max()) < len(self)
            return signers.astype(np.int32, copy=False) if ok else None
        index_of = self.index_of
        try:
            return np.fromiter((index_of[pk] for pk in signers), np.int32, len(signers))
        except KeyError:
            return None

    def points(self, index: np.ndarray) -> list[Point]:
        rows = self.affine[index].tobytes()
        return [
            Point(Fq(int.from_bytes(rows[at : at + 48], "big")),
                  Fq(int.from_bytes(rows[at + 48 : at + 96], "big")), B1)
            for at in range(0, len(rows), 96)
        ]

    def device_limbs(self, mesh=None):
        """(X, Y) u64[N, 13] on the device, or replicated over `mesh`;
        placed at the first call, which waits until they are there."""
        if mesh not in self._limbs:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec

            where = None if mesh is None else NamedSharding(mesh, PartitionSpec())
            with waterfall.leg("key_table.to_device", keys=len(self)) as sp:
                sp.result = self._limbs[mesh] = tuple(
                    jax.device_put(_mont_limbs(self.affine[:, part]), where)
                    for part in (slice(0, 48), slice(48, 96))
                )
            obs.observe("serve.setup_ms.key_table.to_device", sp.seconds * 1e3)
        return self._limbs[mesh]
