"""Batched BLS-scalar-field FFT on device — the DAS recovery kernel.

The 8192-point radix-2 FFT over the 255-bit scalar field is the most
TPU-shaped math in the spec (SURVEY §2.3; reference:
specs/fulu/polynomial-commitments-sampling.md:155-209,779): thousands of
independent butterflies per stage, 13 static stages, no data-dependent
control flow.  Elements live as 9x30-bit limbs in uint64 lanes
(ops/limb_field.py); all log2(n) stages run inside ONE jit with the
stage loop unrolled (static shapes per stage), so XLA fuses the butterfly
chain, and a leading batch axis amortizes recovery over many columns at
once.

Montgomery form begins and ends INSIDE that one program
(:func:`fft_program`): it takes and returns plain (canonical) limbs,
enters with one multiply by ``R^2 mod r`` and leaves with one multiply by
a plain constant, which is also where an inverse transform's ``1/n``
goes. The host (:func:`batch_fft_field`) only reduces ``% r`` and splits
or joins bits, as array operations over the whole flush.

Two boundaries stand over that one program. :func:`batch_fft_field` takes
integers and returns every element as an integer (the blob path, a
single vector). :func:`batch_ifft_folded` takes a data column flush's
cells as the bytes they arrived in and returns one folded row a sidecar
(:func:`fold_program`): the transform is linear, so ``sum_k w_k
IFFT(row_k)`` is ``IFFT(sum_k w_k row_k)``, the rows are weighted and
added BEFORE the stages, and the stages run over a row a sidecar.

Bit-exact with the host oracle crypto/das.fft_field (same DIT butterfly
order: both equal the textbook DFT in exact modular arithmetic)."""

from __future__ import annotations

import os
from functools import lru_cache, partial

import numpy as np

import eth_consensus_specs_tpu  # noqa: F401  (enables x64)
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from eth_consensus_specs_tpu.obs import waterfall

from .limb_field import LimbField

# BLS12-381 scalar field (the polynomial / erasure-coding field)
BLS_MODULUS = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001

FR = LimbField(BLS_MODULUS)


@lru_cache(maxsize=None)
def _bit_reversal_indices(n: int) -> np.ndarray:
    bits = n.bit_length() - 1
    out = np.zeros(n, np.int32)
    for i in range(n):
        out[i] = int(format(i, f"0{bits}b")[::-1], 2) if bits else 0
    return out


@lru_cache(maxsize=None)
def _stage_twiddles(roots: tuple, n: int) -> list[np.ndarray]:
    """Montgomery twiddle tables per DIT stage: stage with half-size m uses
    w[k] = roots[k * (n // (2m))] for k in range(m)."""
    tables = []
    m = 1
    while m < n:
        stride = n // (2 * m)
        tables.append(
            np.stack([FR.to_mont(roots[k * stride] % BLS_MODULUS) for k in range(m)])
        )
        m *= 2
    return tables


def fft_stages(vals, twiddles, n: int):
    """The DIT butterfly stage chain over bit-reversed input — the single
    shared kernel body.

    vals: [B, n, L] Montgomery limbs; twiddles: one [m, L] table per stage."""
    out = vals
    m = 1
    for t in twiddles:
        # [B, n/(2m), 2, m, L]: axis-2 selects the (a, b) halves
        shaped = out.reshape(out.shape[0], n // (2 * m), 2, m, FR.n_limbs)
        a = shaped[:, :, 0]
        b = FR.mont_mul(shaped[:, :, 1], t)  # t broadcasts [m, L]
        merged = jnp.stack([FR.add_mod(a, b), FR.sub_mod(a, b)], axis=2)
        out = merged.reshape(out.shape[0], n, FR.n_limbs)
        m *= 2
    return out


@lru_cache(maxsize=None)
def _device_twiddles(roots: tuple, n: int) -> tuple:
    """The twiddle tables as (uncommitted) device arrays, uploaded once
    per (roots, n) instead of per dispatch; their bytes are booked under
    the ``trusted_setup`` owner in the HBM residency ledger — these are
    the domain constants that live in device memory for the lifetime of
    the process."""
    tables = tuple(jnp.asarray(t) for t in _stage_twiddles(roots, n))
    try:
        from eth_consensus_specs_tpu.obs import ledger

        ledger.register(
            "trusted_setup",
            f"fft_twiddles-{n}",
            sum(int(t.nbytes) for t in tables),
        )
    except Exception:
        pass
    return tables


def fft_program(vals, enter, leave, twiddles, n: int):
    """The whole device program, shared by the single-device and the
    mesh variant: ``mont_mul(vals, enter)``, the stage chain,
    ``mont_mul(., leave)``, one conditional subtraction of r.

    ``mont_mul(x, c) = x * c / R``, so ``enter = R^2 mod r`` lifts plain
    limbs into Montgomery form, and a plain ``leave = s`` drops back out
    of it scaled by ``s`` (1, or ``1/n`` for an inverse transform) in the
    one multiply; ``enter = leave = R mod r`` keeps Montgomery form on
    both sides (:func:`batch_fft_mont`). The product of a value below 2r
    and a constant below r is below 1.5r (R > 4r), so the one
    subtraction makes the output canonical."""
    out = fft_stages(FR.mont_mul(vals, enter), twiddles, n)
    return FR._cond_sub(FR.mont_mul(out, leave), FR.p_limbs)


@lru_cache(maxsize=None)
def _compiled_fft(n: int, n_stages: int):
    """One executable per size; the twiddles and the two boundary
    constants enter as traced args so forward, inverse and coset
    variants, plain or Montgomery limbs, reuse the same compilation. The
    input limb array is DONATED: it is a private bit-reversed copy
    (never reused after the call) and its aval equals the output's, so
    XLA writes the butterfly stages back into the same [B, n, L] buffer —
    at 8192-point DAS batches that halves the kernel's resident
    footprint (the jaxlint donation-audit rule is what flagged the
    missed alias)."""

    @partial(jax.jit, donate_argnums=(0,))
    def run(vals, enter, leave, *twiddles):
        return fft_program(vals, enter, leave, list(twiddles), n)

    return run


def fold_program(words, weights, segments, enter, scale, scale_rows, twiddles, n: int):
    """A data column flush's device program: a row a segment, ``scale[j] *
    IFFT(sum of w_k row_k over the rows k of segment j)``, as canonical
    plain limbs.

    words: u32[B, 8 n], row k's n elements as eight little-endian 32-bit
    words each, canonical, in bit-reversed order; weights: u64[B, L] plain
    limbs below r, zero for a padded row; segments: i32[B] ascending;
    scale: u64[T, n, L] plain limbs below r (an inverse transform's 1/n is
    the caller's to put in them), of which segment j takes row
    ``scale_rows[j]``; enter: ``R^2 mod r``.

    The weights are lifted like the values (one multiply by ``enter``), so
    a weighted element is the plain product in [0, 2r). A segment's rows
    are added as unreduced limbs (each below 2^30, so a lane holds 2^34 of
    them) and swept once: a segment has at most B rows, and ``B * 2r < R``
    is what keeps the sum inside the L limbs and inside what one multiply
    by ``enter`` reduces (``a * b / R + r < 2r`` for ``a < R``, ``b <
    r``). From there it is :func:`fft_program` as every caller runs it,
    over a row a segment, its ``leave`` the segment's scale row."""
    rows = words.shape[0]
    assert rows * 2 * BLS_MODULUS < FR.r_int
    vals = FR.words_to_limbs(words.reshape(rows, n, -1))
    weighted = FR.mont_mul(vals, FR.mont_mul(weights, enter)[:, None, :])
    sums, _carry = FR._carry_sweep(
        jax.ops.segment_sum(
            weighted, segments, num_segments=scale_rows.shape[0], indices_are_sorted=True
        )
    )
    return fft_program(sums, enter, jnp.take(scale, scale_rows, axis=0), twiddles, n)


@lru_cache(maxsize=None)
def _compiled_fold(n: int, n_stages: int):
    """One executable per (rows, segments) shape of :func:`fold_program`.
    The function is called ``run`` like :func:`_compiled_fft`'s: in a
    process that serves data column flushes it is the one program of that
    name a flush executes."""

    @jax.jit
    def run(words, weights, segments, enter, scale, scale_rows, *twiddles):
        return fold_program(words, weights, segments, enter, scale, scale_rows, list(twiddles), n)

    return run


# -- mesh-sharded variant: rows of a batched FFT are independent, so the
# BATCH axis shards with NO collectives (every shard runs the identical
# program over its rows) — byte-identical to the single-device dispatch
# at any shard count. The donated vals buffer aliases per shard exactly
# like the single-device jit.
_SHARDED_FFT: dict[tuple, object] = {}


def _sharded_fft(mesh: Mesh, n: int, n_stages: int):
    key = (mesh, n, n_stages)
    fn = _SHARDED_FFT.get(key)
    if fn is not None:
        return fn
    from eth_consensus_specs_tpu.parallel.mesh_ops import BATCH_AXES

    def local(vals, enter, leave, *twiddles):
        return fft_program(vals, enter, leave, list(twiddles), n)

    fn = jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(BATCH_AXES),) + (P(),) * (2 + n_stages),
            out_specs=P(BATCH_AXES),
            check_vma=False,
        ),
        donate_argnums=(0,),
    )
    _SHARDED_FFT[key] = fn
    return fn


def _clear_sharded_after_fork_in_child() -> None:
    # fork-safety: compiled executables (and cached device twiddle
    # uploads) reference the parent's devices
    _SHARDED_FFT.clear()
    _device_twiddles.cache_clear()


os.register_at_fork(after_in_child=_clear_sharded_after_fork_in_child)


def _dispatch(vals, roots: tuple, enter: int, leave: int, mesh: Mesh | None):
    """[B, n, L] limbs in BIT-REVERSED order -> the program's output,
    natural order, still on the device. With a multi-device `mesh` the
    batch axis shards (B must divide evenly — callers pad rows through
    serve/buckets.fr_fft_key, whose mesh-aware bucket guarantees it)."""
    n = vals.shape[1]
    assert n & (n - 1) == 0 and n == len(roots)
    twiddles = _device_twiddles(roots, n)
    consts = (FR.int_to_limbs(enter), FR.int_to_limbs(leave))
    from eth_consensus_specs_tpu.parallel.mesh_ops import shard_count

    if mesh is not None and shard_count(mesh) > 1:
        from eth_consensus_specs_tpu import obs

        assert vals.shape[0] % shard_count(mesh) == 0
        obs.count("mesh.dispatches", 1)
        obs.count("mesh.sharded_items", int(vals.shape[0]))
        return _sharded_fft(mesh, n, len(twiddles))(vals, *consts, *twiddles)
    return _compiled_fft(n, len(twiddles))(vals, *consts, *twiddles)


def batch_fft_mont(
    vals_mont: jnp.ndarray, roots: tuple, mesh: Mesh | None = None
) -> jnp.ndarray:
    """[B, n, L] Montgomery limbs in [0, 2r) -> DFT in Montgomery limbs
    below r, natural order in and out: the same executable as
    :func:`batch_fft_field`, entered and left with ``R mod r``."""
    rev = jnp.asarray(_bit_reversal_indices(vals_mont.shape[1]))
    one = FR.r_int % BLS_MODULUS
    return _dispatch(jnp.take(vals_mont, rev, axis=1), tuple(roots), one, one, mesh)


def batch_fft_field(
    batches,
    roots_of_unity,
    inv: bool = False,
    mesh: Mesh | None = None,
    pad_batch: int | None = None,
) -> list[list[int]]:
    """Many same-length FFTs at once; bit-exact with crypto/das.fft_field
    applied row-wise (host ints in, host ints out). ``pad_batch`` pads
    the batch axis with zero rows to a bucketed compile shape (the serve
    layer passes its fr_fft_key bucket so accounting and dispatch
    agree); padded rows are discarded.

    No value is in Montgomery form on the host: plain limbs cross the
    boundary both ways, and the program (:func:`fft_program`) enters
    Montgomery form, leaves it and applies an inverse transform's 1/n."""
    roots = tuple(int(r) for r in roots_of_unity)
    n = len(roots)
    b = len(batches)
    with waterfall.leg("fr_fft.pack"):
        flat = [int(x) % BLS_MODULUS for row in batches for x in row]
        assert len(flat) == b * n
        limbs = FR.ints_to_limbs_batch(flat).reshape(b, n, FR.n_limbs)
        padded = b if pad_batch is None else pad_batch
        assert padded >= b
        arr = np.zeros((padded, n, FR.n_limbs), np.uint64)
        arr[:b] = limbs[:, _bit_reversal_indices(n)]
    # host clock round a synced device call: transfer in, the ONE program,
    # transfer out
    with waterfall.leg("fr_fft.call"):
        if inv:
            roots = (roots[0],) + roots[:0:-1]
        leave = pow(n, -1, BLS_MODULUS) if inv else 1
        enter = FR.r_int * FR.r_int % BLS_MODULUS
        out = np.asarray(_dispatch(jnp.asarray(arr), roots, enter, leave, mesh))
    with waterfall.leg("fr_fft.unpack"):
        flat = FR.limbs_to_ints_batch(out[:b])
        return [flat[i * n : (i + 1) * n] for i in range(b)]


def fft_field_device(vals, roots_of_unity, inv: bool = False) -> list[int]:
    """Drop-in device twin of crypto/das.fft_field (single vector)."""
    return batch_fft_field([list(vals)], roots_of_unity, inv=inv)[0]


def cells_to_words(cells: np.ndarray, pad_batch: int) -> np.ndarray:
    """uint8[b, n, 32] big-endian field elements -> u32[pad_batch, 8 n]:
    each element as eight little-endian 32-bit words, least first, zero
    rows up to the bucket. What :func:`fold_program` cuts its limbs from:
    no integer is made of an element."""
    b, n, _ = cells.shape
    assert pad_batch >= b
    words = np.zeros((pad_batch, n, 8), "<u4")
    words[:b] = cells.view(">u4")[:, :, ::-1]
    return words.reshape(pad_batch, 8 * n)


def batch_ifft_folded(
    cells: np.ndarray,
    roots_of_unity,
    weights: np.ndarray,
    segments: np.ndarray,
    scale,
    scale_rows: np.ndarray,
    live: int,
) -> list[list[int]]:
    """Per segment j < live, ``scale[scale_rows[j]] * IFFT(sum_k w_k
    row_k)`` over the rows of the segment, as integers: bit-exact with
    crypto/das.fft_field(inv=True) a row, weighted, added and scaled on
    the host.

    cells: uint8[b, n, 32], row k's elements big-endian, canonical, in
    BIT-REVERSED order (a cell's own bytes); weights: u64[B, L] plain
    limbs, B the row bucket, zero beyond b; segments: i32[B] ascending;
    scale: a resident u64[T, n, L] table of plain limbs that carry the
    1/n; scale_rows: i32[S], S the segment bucket. Bytes go in and S x n
    elements come back: the only integers made are the live ones of
    those."""
    roots = tuple(int(r) for r in roots_of_unity)
    n = len(roots)
    assert cells.shape[1:] == (n, 32) and n & (n - 1) == 0
    with waterfall.leg("fr_fft.pack"):
        words = cells_to_words(cells, weights.shape[0])
    with waterfall.leg("fr_fft.call"):
        twiddles = _device_twiddles((roots[0],) + roots[:0:-1], n)
        enter = FR.int_to_limbs(FR.r_int * FR.r_int % BLS_MODULUS)
        out = np.asarray(
            _compiled_fold(n, len(twiddles))(
                words, weights, segments, enter, scale, scale_rows, *twiddles
            )
        )
    with waterfall.leg("fr_fft.unpack"):
        flat = FR.limbs_to_ints_batch(out[:live])
        return [flat[i * n : (i + 1) * n] for i in range(live)]
