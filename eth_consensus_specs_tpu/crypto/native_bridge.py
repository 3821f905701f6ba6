"""Bridge between the pure-Python crypto oracle and the native BLS12-381
core (native/bls12_381.c).

This is the framework's analogue of the reference's milagro/arkworks seam
(reference: tests/core/pyspec/eth2spec/utils/bls.py:224-296): the Python
tower stays the bit-exact oracle, and every hot operation — scalar
multiplication, subgroup checks, field inversion/sqrt, MSM, the pairing —
transparently routes through the C core when it is available.  Tests force
the pure path with :func:`disabled` and cross-check both sides.

The interface is deliberately raw (Python ints and tuples, not Point/Fq
objects) so this module imports nothing from the field/curve layer and can
be called from anywhere inside it without cycles.  Points at infinity are
``None``; G2 coordinates are ``(c0, c1)`` int pairs.
"""

from __future__ import annotations

import ctypes
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager

from eth_consensus_specs_tpu.analysis import lockwatch
from eth_consensus_specs_tpu.native import get_bls_lib

_enabled: bool | None = None


def enabled() -> bool:
    global _enabled
    if _enabled is None:
        _enabled = get_bls_lib() is not None
    return _enabled


def set_enabled(value: bool) -> None:
    global _enabled
    _enabled = bool(value) and get_bls_lib() is not None


@contextmanager
def disabled():
    """Force the pure-Python path within the context (oracle testing)."""
    global _enabled
    prev = enabled()
    _enabled = False
    try:
        yield
    finally:
        _enabled = prev


# --- encoding helpers ------------------------------------------------------


def _b48(n: int) -> bytes:
    return n.to_bytes(48, "big")


def _g1_buf(p: tuple[int, int] | None) -> tuple[bytes, int]:
    if p is None:
        return b"\x00" * 96, 1
    return _b48(p[0]) + _b48(p[1]), 0


def _g2_buf(p: tuple[tuple[int, int], tuple[int, int]] | None) -> tuple[bytes, int]:
    if p is None:
        return b"\x00" * 192, 1
    (x0, x1), (y0, y1) = p
    return _b48(x0) + _b48(x1) + _b48(y0) + _b48(y1), 0


def _g1_out(out, inf) -> tuple[int, int] | None:
    if inf.value:
        return None
    raw = bytes(out)
    return int.from_bytes(raw[:48], "big"), int.from_bytes(raw[48:], "big")


def _g2_at(raw: bytes, at: int):
    v = [int.from_bytes(raw[at + i * 48 : at + (i + 1) * 48], "big") for i in range(4)]
    return (v[0], v[1]), (v[2], v[3])


def _g2_out(out, inf):
    if inf.value:
        return None
    return _g2_at(bytes(out), 0)


def _buf(data: bytes):
    return (ctypes.c_uint8 * len(data)).from_buffer_copy(data)


# --- group operations ------------------------------------------------------


def g1_mul(p: tuple[int, int] | None, k: int):
    lib = get_bls_lib()
    if p is None or k == 0:
        return None
    neg = k < 0
    if neg:
        k = -k
    sc = k.to_bytes(max(1, (k.bit_length() + 7) // 8), "big")
    buf, inf_in = _g1_buf(p)
    out = (ctypes.c_uint8 * 96)()
    inf = ctypes.c_uint8()
    lib.bls_g1_mul_wide(_buf(buf), inf_in, _buf(sc), len(sc), out, ctypes.byref(inf))
    r = _g1_out(out, inf)
    if r is not None and neg:
        from eth_consensus_specs_tpu.crypto.fields import P

        r = (r[0], (-r[1]) % P)
    return r


def g2_mul(p, k: int):
    lib = get_bls_lib()
    if p is None or k == 0:
        return None
    neg = k < 0
    if neg:
        k = -k
    sc = k.to_bytes(max(1, (k.bit_length() + 7) // 8), "big")
    buf, inf_in = _g2_buf(p)
    out = (ctypes.c_uint8 * 192)()
    inf = ctypes.c_uint8()
    lib.bls_g2_mul_wide(_buf(buf), inf_in, _buf(sc), len(sc), out, ctypes.byref(inf))
    r = _g2_out(out, inf)
    if r is not None and neg:
        from eth_consensus_specs_tpu.crypto.fields import P

        (x, (y0, y1)) = r
        r = (x, ((-y0) % P, (-y1) % P))
    return r


def g1_aggregate(points) -> tuple[int, int] | None:
    lib = get_bls_lib()
    n = len(points)
    bufs = bytearray()
    infs = bytearray()
    for p in points:
        b, i = _g1_buf(p)
        bufs += b
        infs.append(i)
    out = (ctypes.c_uint8 * 96)()
    inf = ctypes.c_uint8()
    lib.bls_g1_aggregate(n, _buf(bytes(bufs)), _buf(bytes(infs)), out, ctypes.byref(inf))
    return _g1_out(out, inf)


def g1_aggregate_affine(points: bytes) -> tuple[int, int] | None:
    """Sum of affine points already in the core's own form (96 bytes
    each, none at infinity), as a resident key table keeps them."""
    n = len(points) // 96
    out = (ctypes.c_uint8 * 96)()
    inf = ctypes.c_uint8()
    get_bls_lib().bls_g1_aggregate(n, _buf(points), _buf(bytes(n)), out, ctypes.byref(inf))
    return _g1_out(out, inf)


# --- slices over the host's cores -------------------------------------------
#
# The core runs without the GIL (ctypes.CDLL), so a call over many
# independent items is cut into slices, each ONE C call on a thread of the
# process's pool. How many follows what the code observes: the items, the
# CPUs this process may run on, and the least a slice holds for a thread to
# pay off, set by an item's cost. A call of one slice runs on the calling
# thread, as it did before the pool. Counter `native.slices.<op>`: the
# slices each call ran on.

_SLICE_FLOOR = {
    # a Miller loop ~0.5 ms; a slice adds 63 fp12 squarings and inversions
    "pairing": 8,
    "g2_decompress": 8,  # sqrt and the psi subgroup check, ~0.3 ms
    "g2_map": 4,  # two SSWU maps, the isogeny, cofactor clearing, ~0.7 ms
    # a Pippenger sum's buckets cost a slice ~1.5 ms whatever it holds
    "g2_msm": 32,
    "g1_key_validate": 256,  # KeyValidate, ~35 us
    "g1_decompress": 256,
}

_POOL: ThreadPoolExecutor | None = None
_POOL_LOCK = lockwatch.wrap(threading.Lock(), "crypto.native_bridge._POOL_LOCK")


def _forget_pool_in_child() -> None:
    # fork-safety: a child has none of the parent's pool threads, and may
    # have been forked while another thread held the lock
    global _POOL, _POOL_LOCK
    _POOL = None
    _POOL_LOCK = lockwatch.wrap(threading.Lock(), "crypto.native_bridge._POOL_LOCK")


os.register_at_fork(after_in_child=_forget_pool_in_child)


def _cpus() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        return os.cpu_count() or 1


def _pool() -> ThreadPoolExecutor:
    global _POOL
    with _POOL_LOCK:
        if _POOL is None:
            _POOL = ThreadPoolExecutor(max_workers=_cpus(), thread_name_prefix="bls-core")
        return _POOL


def _slices(op: str, n: int) -> list[tuple[int, int]]:
    """(start, count) of each slice of `n` items of `op`: a CPU's share of
    the items a slice, but never fewer than the op's floor (the last slice
    holds what is left); one slice where `n` is the floor or less."""
    step = max(-(-n // _cpus()), _SLICE_FLOOR[op])
    return [(at, min(step, n - at)) for at in range(0, n, step)] or [(0, 0)]


def _over_threads(op: str, parts: list[tuple[int, int]], run) -> list:
    """`run(at, count)` for each slice: what each returned, in order."""
    from eth_consensus_specs_tpu import obs

    obs.count(f"native.slices.{op}", len(parts))
    if len(parts) == 1:
        return [run(*parts[0])]
    return list(_pool().map(lambda part: run(*part), parts))


def g1_key_validate_many(keys: bytes) -> tuple[bytes, int]:
    """KeyValidate of the 48-byte compressed public keys laid end to end:
    (their 96-byte affine points end to end, the place of the first key
    that fails or the number of keys when none does). A registry's worth
    is shared out over threads."""
    lib = get_bls_lib()
    n = len(keys) // 48
    out = ctypes.create_string_buffer(96 * n)
    src = ctypes.create_string_buffer(keys, len(keys))
    base_in, base_out = ctypes.addressof(src), ctypes.addressof(out)
    parts = _slices("g1_key_validate", n)
    goods = _over_threads("g1_key_validate", parts, lambda at, count: lib.bls_g1_key_validate_many(
        count, base_in + 48 * at, base_out + 96 * at))
    bad = min((at + good for (at, count), good in zip(parts, goods) if good < count), default=n)
    return out.raw, bad


def g1_decompress_many(points: bytes) -> tuple[bytes, bytes]:
    """The 48-byte compressed G1 points laid end to end, each decided as
    crypto/kzg.validate_kzg_g1 decides it: (their 96-byte affine forms end
    to end, a status byte each: 1 a point of the subgroup, 2 the point at
    infinity in its one encoding, 0 neither). A block's proofs are shared
    out over threads."""
    lib = get_bls_lib()
    n = len(points) // 48
    out = ctypes.create_string_buffer(96 * n)
    status = ctypes.create_string_buffer(n)
    src = ctypes.create_string_buffer(points, len(points))
    base = [ctypes.addressof(b) for b in (src, out, status)]
    _over_threads("g1_decompress", _slices("g1_decompress", n), lambda at, count: (
        lib.bls_g1_decompress_many(count, base[0] + 48 * at, base[1] + 96 * at, base[2] + at)))
    return out.raw, status.raw


def g2_decompress_many(points: bytes) -> tuple[list, bytes]:
    """The 96-byte compressed G2 points laid end to end, each decided as
    :func:`g2_decompress` decides it: (the affine point of each, None where
    it is not a point of the subgroup; a status byte each: 1 such a point,
    2 the point at infinity in its one encoding, 0 neither). A flush's
    signatures are shared out over threads."""
    lib = get_bls_lib()
    n = len(points) // 96
    out = ctypes.create_string_buffer(192 * n)
    status = ctypes.create_string_buffer(n)
    src = ctypes.create_string_buffer(points, len(points))
    base = [ctypes.addressof(b) for b in (src, out, status)]
    _over_threads("g2_decompress", _slices("g2_decompress", n), lambda at, count: (
        lib.bls_g2_decompress_many(count, base[0] + 96 * at, base[1] + 192 * at, base[2] + at)))
    raw, flags = out.raw, status.raw
    return [_g2_at(raw, 192 * k) if flags[k] == 1 else None for k in range(n)], flags


def g2_aggregate(points):
    lib = get_bls_lib()
    n = len(points)
    bufs = bytearray()
    infs = bytearray()
    for p in points:
        b, i = _g2_buf(p)
        bufs += b
        infs.append(i)
    out = (ctypes.c_uint8 * 192)()
    inf = ctypes.c_uint8()
    lib.bls_g2_aggregate(n, _buf(bytes(bufs)), _buf(bytes(infs)), out, ctypes.byref(inf))
    return _g2_out(out, inf)


def g1_msm(points, scalars) -> tuple[int, int] | None:
    lib = get_bls_lib()
    n = len(points)
    bufs = bytearray()
    infs = bytearray()
    scs = bytearray()
    for p, s in zip(points, scalars):
        b, i = _g1_buf(p)
        bufs += b
        infs.append(i)
        scs += (int(s) % (1 << 256)).to_bytes(32, "big")
    out = (ctypes.c_uint8 * 96)()
    inf = ctypes.c_uint8()
    lib.bls_g1_msm(n, _buf(bytes(bufs)), _buf(bytes(infs)), _buf(bytes(scs)), out, ctypes.byref(inf))
    return _g1_out(out, inf)


def g2_msm(points, scalars):
    """Pippenger's sum over each slice of the terms, the slices' sums then
    added: the same point, whatever the slices."""
    lib = get_bls_lib()
    n = len(points)
    bufs = bytearray()
    infs = bytearray()
    scs = bytearray()
    for p, s in zip(points, scalars):
        b, i = _g2_buf(p)
        bufs += b
        infs.append(i)
        scs += (int(s) % (1 << 256)).to_bytes(32, "big")
    src = [ctypes.create_string_buffer(bytes(b), len(b)) for b in (bufs, infs, scs)]
    base = [ctypes.addressof(b) for b in src]

    def run(at, count):
        out = (ctypes.c_uint8 * 192)()
        inf = ctypes.c_uint8()
        lib.bls_g2_msm(count, base[0] + 192 * at, base[1] + at, base[2] + 32 * at, out,
                       ctypes.byref(inf))
        return bytes(out), inf.value

    sums = _over_threads("g2_msm", _slices("g2_msm", n), run)
    if len(sums) == 1:
        raw, inf = sums[0]
        return None if inf else _g2_at(raw, 0)
    out = (ctypes.c_uint8 * 192)()
    inf = ctypes.c_uint8()
    lib.bls_g2_aggregate(len(sums), _buf(b"".join(raw for raw, _ in sums)),
                         _buf(bytes(i for _, i in sums)), out, ctypes.byref(inf))
    return _g2_out(out, inf)


def g2_clear_cofactor(p):
    """[h_eff]P via the Budroni-Pintore endomorphism decomposition —
    bit-identical to the plain scalar multiplication (verified identity)."""
    lib = get_bls_lib()
    if p is None:
        return None
    buf, _ = _g2_buf(p)
    out = (ctypes.c_uint8 * 192)()
    inf = ctypes.c_uint8()
    lib.bls_g2_clear_cofactor(_buf(buf), out, ctypes.byref(inf))
    return _g2_out(out, inf)


def g1_in_subgroup(p: tuple[int, int]) -> bool:
    lib = get_bls_lib()
    buf, _ = _g1_buf(p)
    return bool(lib.bls_g1_in_subgroup(_buf(buf)))


def g2_in_subgroup(p) -> bool:
    lib = get_bls_lib()
    buf, _ = _g2_buf(p)
    return bool(lib.bls_g2_in_subgroup(_buf(buf)))


# --- field operations ------------------------------------------------------


def fq_inv(n: int) -> int:
    lib = get_bls_lib()
    out = (ctypes.c_uint8 * 48)()
    ok = lib.bls_fp_inv(_buf(_b48(n)), out)
    if not ok:
        raise ZeroDivisionError("Fq inverse of zero")
    return int.from_bytes(bytes(out), "big")


def fq2_inv(c0: int, c1: int) -> tuple[int, int]:
    lib = get_bls_lib()
    out = (ctypes.c_uint8 * 96)()
    ok = lib.bls_fp2_inv(_buf(_b48(c0) + _b48(c1)), out)
    if not ok:
        raise ZeroDivisionError("Fq2 inverse of zero")
    raw = bytes(out)
    return int.from_bytes(raw[:48], "big"), int.from_bytes(raw[48:], "big")


def fq_sqrt(n: int) -> int | None:
    lib = get_bls_lib()
    out = (ctypes.c_uint8 * 48)()
    if not lib.bls_fp_sqrt(_buf(_b48(n)), out):
        return None
    return int.from_bytes(bytes(out), "big")


def fq2_sqrt(c0: int, c1: int) -> tuple[int, int] | None:
    lib = get_bls_lib()
    out = (ctypes.c_uint8 * 96)()
    if not lib.bls_fp2_sqrt(_buf(_b48(c0) + _b48(c1)), out):
        return None
    raw = bytes(out)
    return int.from_bytes(raw[:48], "big"), int.from_bytes(raw[48:], "big")


# --- pairing ---------------------------------------------------------------


_FP12_BYTES = 576


def pairing_check_raw(pairs) -> bool:
    """pairs: list of (g1, g2) with g1 = (x, y) | None and
    g2 = ((x0, x1), (y0, y1)) | None."""
    return _pairing_check(_pairing_src(pairs), _slices("pairing", len(pairs)))


def _pairing_src(pairs) -> list:
    """The pairs as the core reads them: G1 points, G2 points, a flags
    byte each (bit 0 the G1 point at infinity, bit 1 the G2 point)."""
    g1s = bytearray()
    g2s = bytearray()
    flags = bytearray()
    for g1, g2 in pairs:
        b1, i1 = _g1_buf(g1)
        b2, i2 = _g2_buf(g2)
        g1s += b1
        g2s += b2
        flags.append(i1 | (i2 << 1))
    return [ctypes.create_string_buffer(bytes(b), len(b)) for b in (g1s, g2s, flags)]


def _pairing_check(src: list, parts: list[tuple[int, int]]) -> bool:
    """The check of the pairs in `src` (G1 points, G2 points, flags) over
    the slices `parts`: ONE call where there is one slice; else each
    slice's Miller value on a thread and their product through ONE final
    exponentiation. A slice that cannot run fails the check CLOSED."""
    lib = get_bls_lib()
    g1, g2, flags = (ctypes.addressof(b) for b in src)
    if len(parts) == 1:
        return bool(_over_threads("pairing", parts, lambda at, count: lib.bls_pairing_check(
            count, g1 + 96 * at, g2 + 192 * at, flags + at))[0])

    def miller(at, count):
        f = ctypes.create_string_buffer(_FP12_BYTES)
        return lib.bls_pairing_miller(count, g1 + 96 * at, g2 + 192 * at, flags + at, f), f.raw

    millers = _over_threads("pairing", parts, miller)
    if not all(ok for ok, _ in millers):
        return False
    return bool(lib.bls_pairing_final_check(len(millers), b"".join(f for _, f in millers)))


def g2_prepare_many(points) -> "np.ndarray | None":
    """Batched native producer of the device Miller kernel's per-step line
    coefficients (the C side of ops/pairing_device: one lockstep affine ate
    walk across all points with Montgomery batch inversions, emitting limbs
    already in the device's 2^390-Montgomery 26-bit encoding).

    points: list of ((x0, x1), (y0, y1)) affine subgroup G2 points (no
    infinities — callers mask those out).  Returns u64[n, N_STEPS, 2, 2, 15]
    or None when the native core is unavailable or the walk degenerated
    (callers fall back to the per-point host oracle prepare_g2)."""
    import numpy as np

    if not enabled() or not points:
        return None
    lib = get_bls_lib()
    if lib is None or not hasattr(lib, "bls_g2_prepare_many"):
        return None
    n = len(points)
    g2s = bytearray()
    for g2 in points:
        b2, i2 = _g2_buf(g2)
        if i2:
            return None
        g2s += b2
    n_steps = 68  # 63 doublings + 5 additions (low set bits of |x|)
    out = (ctypes.c_uint64 * (n * n_steps * 2 * 2 * 15))()
    written = lib.bls_g2_prepare_many(
        ctypes.c_uint64(n), _buf(bytes(g2s)), out
    )
    if written != n_steps:
        return None
    return np.frombuffer(out, dtype=np.uint64).reshape(n, n_steps, 2, 2, 15).copy()


def pairing_gt_coeffs(g1, g2) -> list[tuple[int, int]]:
    """Full pairing; returns the six flattened w^i Fq2 coefficients of the
    GT element (exact value — matches the Python oracle bit-for-bit)."""
    lib = get_bls_lib()
    b1, i1 = _g1_buf(g1)
    b2, i2 = _g2_buf(g2)
    assert not i1 and not i2, "pairing_gt_coeffs expects affine inputs"
    out = (ctypes.c_uint8 * 576)()
    lib.bls_pairing(_buf(b1), _buf(b2), out)
    raw = bytes(out)
    return [
        (
            int.from_bytes(raw[96 * i : 96 * i + 48], "big"),
            int.from_bytes(raw[96 * i + 48 : 96 * i + 96], "big"),
        )
        for i in range(6)
    ]


# --- RFC 9380 G2 map stage -------------------------------------------------

_map_params_sent = False


def g2_map_set_params(blob: bytes) -> None:
    """Ship the SSWU/isogeny ciphersuite constants (18 Fq2 values, 96 bytes
    each: A', B', Z, K1[0..3], K2[0..2], K3[0..3], K4[0..3]) into the C
    core. The Python copies are structurally validated at import
    (crypto/hash_to_curve.py _validate_ciphersuite)."""
    global _map_params_sent
    lib = get_bls_lib()
    assert len(blob) == 18 * 96
    lib.bls_g2_map_set_params(_buf(blob))
    _map_params_sent = True


def g2_map_params_sent() -> bool:
    return _map_params_sent


def g2_map_from_fields(u0: tuple[int, int], u1: tuple[int, int]):
    """SSWU + 3-isogeny + cofactor clearing for two hash_to_field outputs.
    Returns the affine E2 point (or None for infinity)."""
    lib = get_bls_lib()
    buf = _b48(u0[0]) + _b48(u0[1]) + _b48(u1[0]) + _b48(u1[1])
    out = (ctypes.c_uint8 * 192)()
    inf = ctypes.c_uint8()
    rc = lib.bls_g2_map_from_fields(_buf(buf), out, ctypes.byref(inf))
    if rc != 0:
        raise RuntimeError("bls_g2_map_from_fields called before set_params")
    return _g2_out(out, inf)


def g2_map_from_fields_many(fields: list) -> list:
    """:func:`g2_map_from_fields` of each (u0, u1), shared out over
    threads; the parameters must have been sent before."""
    lib = get_bls_lib()
    n = len(fields)
    data = b"".join(_b48(u0[0]) + _b48(u0[1]) + _b48(u1[0]) + _b48(u1[1]) for u0, u1 in fields)
    src = ctypes.create_string_buffer(data, len(data))
    out = ctypes.create_string_buffer(192 * n)
    inf = ctypes.create_string_buffer(n)
    base = [ctypes.addressof(b) for b in (src, out, inf)]
    rcs = _over_threads("g2_map", _slices("g2_map", n), lambda at, count: (
        lib.bls_g2_map_from_fields_many(count, base[0] + 192 * at, base[1] + 192 * at,
                                        base[2] + at)))
    if any(rcs):
        raise RuntimeError("bls_g2_map_from_fields_many called before set_params")
    raw, infs = out.raw, inf.raw
    return [None if infs[k] else _g2_at(raw, 192 * k) for k in range(n)]


def g2_decompress(data: bytes):
    """Full IETF G2 decompression (x parse + sqrt + sign + subgroup) in one
    native call. Returns the affine point tuple, None for the canonical
    infinity encoding; raises ValueError on malformed/out-of-subgroup input
    (mirroring curve.g2_from_bytes)."""
    lib = get_bls_lib()
    out = (ctypes.c_uint8 * 192)()
    inf = ctypes.c_uint8()
    ok = lib.bls_g2_decompress(_buf(bytes(data)), out, ctypes.byref(inf))
    if not ok:
        raise ValueError("invalid G2 compressed encoding")
    return _g2_out(out, inf)
