"""The kernel registry: every registered device kernel, declaratively.

jaxlint (analysis/jaxlint.py) abstract-evals each entry here — no
execution, no XLA compile — and runs its trace-level rules over the
jaxprs. The registry is therefore the place where a kernel family makes
its accelerator contract EXPLICIT:

  * ``dtypes`` — the aval dtypes the kernel is allowed to contain
    (x64-drift: an i64 counter inside a uint32 hash kernel doubles its
    register/HBM footprint silently);
  * ``donate`` / ``donation_waiver`` — every family must either declare
    the flat argnums its jit actually donates, or carry a reviewed
    waiver string saying why no donation opportunity is taken
    (donation-audit; the ROADMAP item-2 device-resident state work
    lands behind this seam). The registry refuses entries that declare
    neither — silence is not a donation policy;
  * ``variants`` — the representative traced shapes, including the
    mesh-sharded variant where one exists (collective-audit needs the
    real shard_map mesh to bind axis names against);
  * ``key_grid`` — for kernels the serve layer buckets, the LIVE
    compile-key function (serve/buckets.merkle_many_key / bls_msm_key,
    ops/state_root.state_root_compile_key — the same callables the
    dispatch sites use, not copies) evaluated over the bucket grid so
    the recompile-surface rule can prove key -> traced-signature
    injectivity.

Representative shapes are small on purpose: ``jax.make_jaxpr`` cost is
graph-size-bound, not data-bound, so a depth-10 tree over 8 trees
exercises exactly the primitives the depth-12x64 production bucket
compiles. The bucket GRIDS (key_grid) do cover the production range —
key computation is pure python.

``suppress`` mirrors speclint's inline ``# speclint: disable=`` escape
hatch at registry granularity: a reviewed, diff-visible waiver of one
rule for one kernel. The baseline (jaxlint_baseline.json) ships EMPTY.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

from .ranges import Domain, Wrap

# ---------------------------------------------------------------- specs --


@dataclass(frozen=True)
class Variant:
    """One traceable entry point of a kernel family: the callable plus
    the abstract args (ShapeDtypeStruct pytrees) to trace it with.

    ``domains`` seed rangelint's interval analysis: one
    :class:`~eth_consensus_specs_tpu.analysis.ranges.Domain` per TRACED
    input pytree leaf (flatten order, static argnums excluded), each
    carrying the inclusive elementwise bound the kernel assumes of that
    argument ("Montgomery limbs in [0, 2p) limb-wise", "scalar bits in
    {0, 1}") plus the concrete boundary members
    (tests/test_range_domains.py executes every family at these corners
    against its host oracle, so a stale domain fails at runtime too,
    not just in the prover)."""

    label: str  # "single" | "mesh"
    fn: Callable
    args: tuple
    static_argnums: tuple[int, ...] = ()
    mesh: object = None  # jax Mesh for mesh variants (axis-name binding)
    domains: tuple = ()  # one ranges.Domain per traced input leaf


@dataclass(frozen=True)
class KernelSpec:
    name: str
    help: str
    # aval dtypes the kernel's jaxpr may contain (0-d weak-typed scalars
    # — literal-derived trace constants — are exempt in the rule)
    dtypes: frozenset
    # flat positional argnums the kernel's jit declares donated
    donate: tuple[int, ...] = ()
    # reviewed reason why donation opportunities are NOT taken (required
    # when donate is empty — the registry refuses silent entries)
    donation_waiver: str | None = None
    # registry-level rule suppressions (reviewed escape hatch)
    suppress: tuple[str, ...] = ()
    # sanctioned-wraparound primitive sites for rangelint: each Wrap
    # names ONE primitive at ONE ``file.py::function`` site where
    # exceeding the lane is the algorithm (sha256's mod-2^32 adds, the
    # borrow-chain transient underflow) — reviewed per site, never
    # blanket
    wraps: tuple = ()
    # (mesh | None) -> list[Variant]; mesh variants only when mesh given
    # — whether a family HAS a mesh variant is determined here and only
    # here (callers inspect Variant.mesh; no duplicate flag to drift)
    build_variants: Callable = None
    # (mesh | None) -> list[(key tuple, signature tuple)] over the
    # serve bucket grid; None = the serve layer never keys this kernel
    key_grid: Callable | None = None


def _sds(shape, dtype):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, getattr(jnp, dtype))


def _default_buckets() -> tuple[int, ...]:
    from eth_consensus_specs_tpu.serve.config import ServeConfig

    return ServeConfig().buckets


# ------------------------------------------------------- range domains --


def limb_caps(value_max: int, limb_bits: int, n_limbs: int):
    """Inclusive per-limb bound for base-2^limb_bits digit vectors of
    values <= value_max. Elementwise on purpose: the top limb of a
    value < 2p is ~2^22, not the limb mask, and several overflow proofs
    (fat-p lend cover, column sums) need exactly that precision."""
    import numpy as np

    mask = (1 << limb_bits) - 1
    return np.array(
        [min(mask, value_max >> (limb_bits * i)) for i in range(n_limbs)],
        dtype=object,
    )


def limb_digits(x: int, limb_bits: int, n_limbs: int):
    """Concrete digit vector of x (a VALID domain member — corner data)."""
    import numpy as np

    mask = (1 << limb_bits) - 1
    return np.array(
        [(x >> (limb_bits * i)) & mask for i in range(n_limbs)], dtype=np.uint64
    )


def mont_domain(
    name: str, modulus: int, limb_bits: int, n_limbs: int, *, canonical: bool = False
) -> Domain:
    """Montgomery limb vectors, limb-wise. The default is the REDUNDANT
    range [0, 2p) every reduced device field element satisfies; pass
    ``canonical=True`` for boundaries that require host-converted
    elements < p (the pairing's prepared inputs: ``_fat_p``'s top-limb
    lend cover is sized from ``val=p-1``, and rangelint proves a
    [p, 2p) input would underflow it — the declaration IS the
    precondition). Corners are the boundary members of the range."""
    vmax = (modulus - 1) if canonical else (2 * modulus - 1)
    corners = (
        ("zero", 0),
        ("p-1", limb_digits(modulus - 1, limb_bits, n_limbs)),
    )
    if not canonical:
        corners += (("2p-1", limb_digits(2 * modulus - 1, limb_bits, n_limbs)),)
    return Domain(name, hi=limb_caps(vmax, limb_bits, n_limbs), corners=corners)


def limb_borrow_wraps(file: str, mask: int) -> tuple:
    """The reviewed wrap pair for a borrow-chain subtraction: the
    ``x - y - borrow`` step transiently underflows (two's complement, by
    design) and the restore add ``cur + (under << LIMB_BITS)`` provably
    lands back under the limb ``mask`` — the carry-separation argument
    the mask-consistency rule checks."""
    return (
        Wrap("sub", f"{file}::_sub_limbs"),
        Wrap("add", f"{file}::_sub_limbs", bound=mask),
    )


def lazy_lend_wraps() -> tuple:
    """lazy_limbs sanctioned sites: the borrow chain (shrink's cond-sub)
    plus the ``sub`` lend path. ``fat - y`` is sound because a
    NORMALIZED y's top digit is bounded by ``y.val >> 364`` — a
    value-level fact the interval domain cannot represent after norm's
    re-masking — so the site is declared trusted with the bound
    ``lazy_limbs._LEND_LIMB_CAP`` (1 << 30) that ``sub`` now enforces at
    trace time on every call — auto-shrinking a subtrahend whose fat
    cover would exceed it (tests pin the two constants equal)."""
    return limb_borrow_wraps("lazy_limbs.py", _MASK26) + (
        Wrap("sub", "lazy_limbs.py::sub", bound=1 << 30),
    )


# u32 hash words: the full lane is the domain (message/chunk words)
_WORDS32 = Domain(
    "hash words (full u32 lane)",
    hi=0xFFFFFFFF,
    corners=(("zero", 0), ("all-ones", 0xFFFFFFFF)),
)

# sha256 wraps BY DESIGN: every add is mod 2^32 (the algorithm), and
# _rotr's left shift drops high bits that the or re-introduces rotated.
# Declared per primitive site; families that hash (merkle, state_root)
# reach these frames through their call stacks.
_SHA_WRAPS = (
    Wrap("add", "sha256.py::_compress"),
    Wrap("add", "sha256.py::rnd"),
    Wrap("add", "sha256.py::_compress_scan"),
    Wrap("add", "sha256.py::sha256_pair_words_scan"),
    Wrap("add", "sha256.py::sha256_pair_words_unrolled"),
    Wrap("add", "sha256.py::sha256_single_block"),
    Wrap("shift_left", "sha256.py::_rotr"),
)


# ------------------------------------------------------------- builders --


def _sha256_variants(mesh):
    from eth_consensus_specs_tpu.ops import sha256

    return [
        Variant(
            f"single:tile{t}",
            sha256._kernel,
            (_sds((t, 16), "uint32"),),
            domains=(_WORDS32,),
        )
        for t in sha256.TILES
    ]


def _merkle_variants(mesh):
    from eth_consensus_specs_tpu.ops import merkle

    return [
        Variant(
            f"single:d{d}",
            merkle._tree_root_fused,
            (_sds((1 << d, 8), "uint32"), d),
            static_argnums=(1,),
            domains=(_WORDS32,),
        )
        for d in (6, 10)
    ]


def _merkle_many_args(batch: int, depth: int):
    return (_sds((batch, 1 << depth, 8), "uint32"),)


def _merkle_many_variants(mesh):
    from eth_consensus_specs_tpu.ops import merkle
    from eth_consensus_specs_tpu.parallel import mesh_ops

    depth = 10
    out = [
        Variant(
            "single",
            merkle._many_tree_root_fused,
            (*_merkle_many_args(8, depth), depth),
            static_argnums=(1,),
            domains=(_WORDS32,),
        )
    ]
    if mesh is not None:
        batch = mesh_ops.pad_to_shards(8, mesh_ops.shard_count(mesh))
        out.append(
            Variant(
                "mesh",
                merkle._many_tree_root_sharded(mesh, depth),
                _merkle_many_args(batch, depth),
                mesh=mesh,
                domains=(_WORDS32,),
            )
        )
    return out


def _merkle_many_key_grid(mesh):
    """LIVE serve key fn (buckets.merkle_many_key) over the bucket grid
    vs the traced signature the dispatch actually compiles under."""
    from eth_consensus_specs_tpu.parallel import mesh_ops
    from eth_consensus_specs_tpu.serve import buckets

    cfg = _default_buckets()
    out = []
    for m in (None, mesh) if mesh is not None else (None,):
        shards = mesh_ops.shard_count(m)
        for depth in (4, 10, 12):
            for n in (1, 2, 3, 5, 8, 13, 16, 33, 64):
                key = buckets.merkle_many_key(n, depth, cfg, mesh=m)
                pad = key[1]
                batch = mesh_ops.pad_to_shards(pad, shards) if m is not None else pad
                sig = (
                    _canon_args(_merkle_many_args(batch, depth)),
                    depth,
                    mesh_ops.mesh_signature(m),
                )
                out.append((key, sig))
                # the router's profile-form of the SAME key fn (the
                # front door predicts siblings' compile keys from
                # (shards, signature) — serve/buckets): a divergence
                # between the two forms is an `aliased` finding here,
                # not a silent cold compile in production
                out.append((
                    buckets.merkle_many_key_from_profile(
                        n, depth, cfg, shards, mesh_ops.mesh_signature(m)
                    ),
                    sig,
                ))
    return out


def _merkle_inc_args(shards: int, depth_local: int):
    m = (1 << (depth_local + 1)) - 1
    ll = 1 << depth_local
    return (
        _sds((shards, m, 8), "uint32"),
        _sds((shards, ll), "bool_"),
        _sds((shards, ll, 8), "uint32"),
    )


def _merkle_inc_variants(mesh):
    from eth_consensus_specs_tpu.ops import merkle_inc
    from eth_consensus_specs_tpu.serve import buckets

    depth, cap = 10, 8
    doms = (_WORDS32, _BOOL_DOMAIN, _WORDS32)
    out = [
        Variant(
            "single",
            merkle_inc._apply_kernel(depth, cap, buckets.inc_dense_count(depth, cap)),
            _merkle_inc_args(1, depth),
            domains=doms,
        )
    ]
    if mesh is not None:
        shards = merkle_inc.forest_shards(depth, mesh)
        if shards > 1:
            dl = depth - (shards - 1).bit_length()
            out.append(
                Variant(
                    "mesh",
                    merkle_inc._apply_kernel_mesh(
                        mesh, depth, cap, buckets.inc_dense_count(dl, cap)
                    ),
                    _merkle_inc_args(shards, dl),
                    mesh=mesh,
                    domains=doms,
                )
            )
    return out


def _merkle_inc_key_grid(mesh):
    """LIVE serve key fn (buckets.merkle_inc_key) over the dirty-bucket
    grid vs the traced forest-update signature the dispatch compiles
    under (every static knob — capacity, dense threshold, depth, mesh
    signature — discriminates)."""
    from eth_consensus_specs_tpu.ops import merkle_inc
    from eth_consensus_specs_tpu.parallel import mesh_ops
    from eth_consensus_specs_tpu.serve import buckets

    out = []
    for m in (None, mesh) if mesh is not None else (None,):
        for depth in (8, 10, 12):
            shards = merkle_inc.forest_shards(depth, m) if m is not None else 1
            dl = depth - (shards - 1).bit_length()
            for hint in (1, 5, 8, 64, 200):
                cap = min(buckets.inc_dirty_bucket(hint), 1 << dl)
                dense = buckets.inc_dense_count(dl, cap)
                key = buckets.merkle_inc_key(cap, dense, depth, mesh=m)
                sig = (
                    _canon_args(_merkle_inc_args(shards, dl)),
                    cap,
                    dense,
                    mesh_ops.mesh_signature(m),
                )
                out.append((key, sig))
    return out


def _shuffle_variants(mesh):
    from eth_consensus_specs_tpu.ops import shuffle

    lanes, rounds = 512, 90
    return [
        Variant(
            "single",
            shuffle.shuffle_rounds_kernel,
            (
                _sds((8,), "uint32"),
                _sds((rounds,), "int32"),
                _sds((), "int32"),
                _sds((lanes,), "int32"),
            ),
            domains=(
                _WORDS32,
                Domain(
                    "round pivots in [0, n)",
                    hi=lanes - 1,
                    corners=(("zero", 0), ("n-1", lanes - 1)),
                ),
                Domain("active count in [1, lanes]", lo=1, hi=lanes, corners=(("one", 1), ("full", lanes))),
                Domain(
                    "registry indices below 2**31",
                    hi=(1 << 31) - 1,
                    corners=(("zero", 0), ("2**31-1", (1 << 31) - 1)),
                ),
            ),
        )
    ]


def _fr_fft_args(batch: int, n: int, stages: int):
    from eth_consensus_specs_tpu.ops import fr_fft

    fr = fr_fft.FR
    return (
        _sds((batch, n, fr.n_limbs), "uint64"),
        # enter, leave: the two boundary constants (ops/fr_fft.fft_program)
        _sds((fr.n_limbs,), "uint64"),
        _sds((fr.n_limbs,), "uint64"),
        *(_sds((1 << i, fr.n_limbs), "uint64") for i in range(stages)),
    )


def _das_fold_args(rows: int, segments: int, n: int, stages: int):
    """ops/fr_fft.fold_program's arguments: a flush's cells as 32-bit
    words, a weight and a segment id a row, ``enter``, the coset unshift
    table (a row a column index, 128) and a row of it a segment, the
    twiddles."""
    from eth_consensus_specs_tpu.ops import fr_fft

    limbs = fr_fft.FR.n_limbs
    return (
        _sds((rows, 8 * n), "uint32"),
        _sds((rows, limbs), "uint64"),
        _sds((rows,), "int32"),
        _sds((limbs,), "uint64"),
        _sds((128, n, limbs), "uint64"),
        _sds((segments,), "int32"),
        *(_sds((1 << i, limbs), "uint64") for i in range(stages)),
    )


def _fr_fft_variants(mesh):
    from eth_consensus_specs_tpu.ops import fr_fft
    from eth_consensus_specs_tpu.parallel import mesh_ops

    n, stages = 256, 8
    fr = fr_fft.FR
    # twiddle tables are CANONICAL Montgomery (< r, built by to_mont);
    # no corners — the runtime corner test needs the real tables (a
    # boundary "twiddle" would just be a different polynomial basis)
    tw_dom = Domain(
        "twiddles: canonical Montgomery Fr (< r limb-wise)",
        hi=limb_caps(fr.modulus - 1, 30, fr.n_limbs),
    )
    # enter / leave: R^2, R, 1 or 1/n mod r, host-built, always < r
    const_dom = Domain(
        "boundary constant: canonical Fr (< r limb-wise)",
        hi=limb_caps(fr.modulus - 1, 30, fr.n_limbs),
    )
    doms = (
        # plain limbs < r on the served path; batch_fft_mont hands the
        # same program Montgomery limbs in the redundant range
        mont_domain("values: Fr limbs in [0, 2r)", fr.modulus, 30, fr.n_limbs),
        const_dom,
        const_dom,
        *([tw_dom] * stages),
    )
    out = [
        Variant(
            "single",
            fr_fft._compiled_fft(n, stages),
            _fr_fft_args(4, n, stages),
            domains=doms,
        )
    ]
    if mesh is not None:
        batch = mesh_ops.pad_to_shards(4, mesh_ops.shard_count(mesh))
        out.append(
            Variant(
                "mesh",
                fr_fft._sharded_fft(mesh, n, stages),
                _fr_fft_args(batch, n, stages),
                mesh=mesh,
                domains=doms,
            )
        )
    return out


def _fr_fft_key_grid(mesh):
    """LIVE serve key fn (buckets.fr_fft_key) over the blob-flush grid
    vs the batch-padded avals the FFT dispatch compiles under — the
    bucket discipline the FFT never had before the DAS workload."""
    from eth_consensus_specs_tpu.parallel import mesh_ops
    from eth_consensus_specs_tpu.serve import buckets

    out = []
    for m in (None, mesh) if mesh is not None else (None,):
        shards = mesh_ops.shard_count(m)
        for n in (256, 4096):
            stages = n.bit_length() - 1
            for b in (1, 2, 3, 5, 8, 16, 33):
                key = buckets.fr_fft_key(b, n, mesh=m)
                sig = (
                    _canon_args(_fr_fft_args(key[1], n, stages)),
                    mesh_ops.mesh_signature(m),
                )
                out.append((key, sig))
                # profile-form agreement (see _merkle_many_key_grid)
                out.append((
                    buckets.fr_fft_key_from_profile(
                        b, n, shards, mesh_ops.mesh_signature(m)
                    ),
                    sig,
                ))
    return out


def _fq_jacobian_domains() -> tuple:
    from eth_consensus_specs_tpu.crypto.fields import P

    return tuple(
        mont_domain(f"Jacobian {c}: Montgomery Fq in [0, 2p)", P, 30, 13)
        for c in ("X", "Y", "Z")
    )


_SCALAR_BITS_DOMAIN = Domain(
    "scalar bits in {0, 1}",
    hi=1,
    corners=(("zero", 0), ("one", 1)),
)


def _g1_msm_variants(mesh):
    from eth_consensus_specs_tpu.ops import g1_msm
    from eth_consensus_specs_tpu.parallel import mesh_ops

    def args(lanes):
        return (
            _sds((lanes, 256), "uint64"),
            *[_sds((lanes, 13), "uint64")] * 3,
        )

    doms = (_SCALAR_BITS_DOMAIN, *_fq_jacobian_domains())
    out = [Variant("single", g1_msm.msm_kernel, args(8), domains=doms)]
    if mesh is not None:
        lanes = g1_msm.mesh_lane_pad(8, mesh_ops.shard_count(mesh))
        out.append(
            Variant(
                "mesh",
                g1_msm._sharded_fn(mesh, "msm"),
                args(lanes),
                mesh=mesh,
                domains=doms,
            )
        )
    return out


def _bls_msm_args(items: int, lanes: int):
    return tuple([_sds((items, lanes, 13), "uint64")] * 3)


def _bls_msm_variants(mesh):
    from eth_consensus_specs_tpu.ops import g1_msm
    from eth_consensus_specs_tpu.parallel import mesh_ops

    doms = _fq_jacobian_domains()
    out = [
        Variant(
            "single", g1_msm.sum_many_kernel, _bls_msm_args(4, 8), domains=doms
        )
    ]
    if mesh is not None:
        items = mesh_ops.pad_to_shards(4, mesh_ops.shard_count(mesh))
        out.append(
            Variant(
                "mesh",
                g1_msm._sharded_fn(mesh, "sum_many"),
                _bls_msm_args(items, 8),
                mesh=mesh,
                domains=doms,
            )
        )
    return out


def _bls_msm_key_grid(mesh):
    """LIVE serve key fn (buckets.bls_msm_key) over the committee grid
    vs the many_sum_shape padded avals the dispatch compiles under."""
    from eth_consensus_specs_tpu.ops.g1_msm import many_sum_shape
    from eth_consensus_specs_tpu.parallel import mesh_ops
    from eth_consensus_specs_tpu.serve import buckets

    out = []
    for m in (None, mesh) if mesh is not None else (None,):
        shards = mesh_ops.shard_count(m)
        for items in (1, 2, 3, 5, 9, 16, 33):
            for lanes in (1, 3, 8, 64, 100):
                key = buckets.bls_msm_key(items, lanes, mesh=m)
                item_pad, lane_pad = many_sum_shape(items, lanes, shards)
                sig = (
                    _canon_args(_bls_msm_args(item_pad, lane_pad)),
                    mesh_ops.mesh_signature(m),
                )
                out.append((key, sig))
                # profile-form agreement (see _merkle_many_key_grid)
                out.append((
                    buckets.bls_msm_key_from_profile(
                        items, lanes, shards, mesh_ops.mesh_signature(m)
                    ),
                    sig,
                ))
    return out


def _kzg_msm_args(items: int, lanes: int):
    return (
        _sds((items, lanes, 256), "uint64"),
        *[_sds((items, lanes, 13), "uint64")] * 3,
    )


def _kzg_msm_variants(mesh):
    from eth_consensus_specs_tpu.ops import g1_msm

    doms = (_SCALAR_BITS_DOMAIN, *_fq_jacobian_domains())
    out = [
        Variant(
            "single", g1_msm.msm_many_kernel, _kzg_msm_args(2, 4), domains=doms
        )
    ]
    if mesh is not None:
        from eth_consensus_specs_tpu.parallel import mesh_ops

        lanes = g1_msm.mesh_lane_pad(4, mesh_ops.shard_count(mesh))
        out.append(
            Variant(
                "mesh",
                g1_msm._sharded_fn(mesh, "msm_many"),
                _kzg_msm_args(2, lanes),
                mesh=mesh,
                domains=doms,
            )
        )
    return out


def _kzg_msm_key_grid(mesh):
    """LIVE serve key fn (buckets.kzg_msm_key) over the blob-flush grid
    vs the 2-item lane-padded avals the RLC fold compiles under (the
    lane axis is the mesh-sharded one, like g2_agg)."""
    from eth_consensus_specs_tpu.parallel import mesh_ops
    from eth_consensus_specs_tpu.serve import buckets

    out = []
    for m in (None, mesh) if mesh is not None else (None,):
        shards = mesh_ops.shard_count(m)
        for n in (1, 2, 3, 5, 9, 16, 33, 64):
            key = buckets.kzg_msm_key(n, mesh=m)
            sig = (
                _canon_args(_kzg_msm_args(2, buckets.kzg_lane_bucket(n, shards))),
                mesh_ops.mesh_signature(m),
            )
            out.append((key, sig))
            # profile-form agreement (see _merkle_many_key_grid)
            out.append((
                buckets.kzg_msm_key_from_profile(
                    n, shards, mesh_ops.mesh_signature(m)
                ),
                sig,
            ))
    return out


def _g2_agg_args(items: int, lanes: int):
    from eth_consensus_specs_tpu.ops import lazy_limbs as lz

    return tuple([_sds((items, lanes, 2, lz.N_LIMBS), "uint64")] * 3)


def _g2_agg_domains() -> tuple:
    from eth_consensus_specs_tpu.crypto.fields import P
    from eth_consensus_specs_tpu.ops import lazy_limbs as lz

    # REDUNDANT [0, 2p): host conversion feeds canonical (< p) limbs,
    # but the butterfly scan's canonical carry is < 2p and the declared
    # domain must cover what actually crosses the boundary
    return tuple(
        mont_domain(
            f"G2 Jacobian {c}: Montgomery Fq2 in [0, 2p) limb-wise",
            P, lz.LIMB_BITS, lz.N_LIMBS,
        )
        for c in ("X", "Y", "Z")
    )


def _g2_agg_variants(mesh):
    from eth_consensus_specs_tpu.ops import g2_aggregate as ga
    from eth_consensus_specs_tpu.serve import buckets

    doms = _g2_agg_domains()
    out = [
        Variant("single", ga.g2_sum_many_kernel, _g2_agg_args(2, 4), domains=doms)
    ]
    if mesh is not None:
        from eth_consensus_specs_tpu.parallel import mesh_ops

        lanes = buckets.agg_lane_bucket(4, mesh_ops.shard_count(mesh))
        out.append(
            Variant(
                "mesh",
                ga._sharded_fn(mesh),
                _g2_agg_args(2, lanes),
                mesh=mesh,
                domains=doms,
            )
        )
    return out


def _g2_agg_key_grid(mesh):
    """LIVE serve key fn (buckets.g2_agg_key) over the committee grid
    vs the g2_many_sum_shape padded avals the dispatch compiles under
    (the lane axis is the mesh-sharded one here)."""
    from eth_consensus_specs_tpu.ops.g2_aggregate import g2_many_sum_shape
    from eth_consensus_specs_tpu.parallel import mesh_ops
    from eth_consensus_specs_tpu.serve import buckets

    out = []
    for m in (None, mesh) if mesh is not None else (None,):
        shards = mesh_ops.shard_count(m)
        for items in (1, 2, 3, 5, 9, 16, 33):
            for lanes in (1, 3, 8, 64, 100):
                key = buckets.g2_agg_key(items, lanes, mesh=m)
                item_pad, lane_pad = g2_many_sum_shape(items, lanes, shards)
                sig = (
                    _canon_args(_g2_agg_args(item_pad, lane_pad)),
                    mesh_ops.mesh_signature(m),
                )
                out.append((key, sig))
                # profile-form agreement (see _merkle_many_key_grid)
                out.append((
                    buckets.g2_agg_key_from_profile(
                        items, lanes, shards, mesh_ops.mesh_signature(m)
                    ),
                    sig,
                ))
    return out


def _pairing_domains() -> tuple:
    from eth_consensus_specs_tpu.crypto.fields import P
    from eth_consensus_specs_tpu.ops import lazy_limbs as lz

    # CANONICAL (< p): miller_from_coeffs claims val=P-1 for the
    # prepared inputs, and _fat_p's lend cover is sized from that claim
    # — a [p, 2p) input would underflow the borrow-free sub
    lazy = lambda name: mont_domain(name, P, lz.LIMB_BITS, lz.N_LIMBS, canonical=True)
    return (
        lazy("prepared coefficients: canonical Montgomery Fq (< p)"),
        lazy("G1 x: canonical Montgomery Fq (< p)"),
        lazy("G1 y: canonical Montgomery Fq (< p)"),
        Domain("active mask", hi=1, corners=(("inactive", 0), ("active", 1))),
    )


def _pairing_variants(mesh):
    from eth_consensus_specs_tpu.ops import pairing_device as pd

    def chunk_args(n_chunks):
        lead = (n_chunks,) if n_chunks else ()
        return (
            _sds((*lead, pd._CHUNK, pd.N_STEPS, 2, 2, pd.N_LIMBS), "uint64"),
            _sds((*lead, pd._CHUNK, pd.N_LIMBS), "uint64"),
            _sds((*lead, pd._CHUNK, pd.N_LIMBS), "uint64"),
            _sds((*lead, pd._CHUNK), "bool"),
        )

    doms = _pairing_domains()
    out = [Variant("single", pd._miller_chunk_fold, chunk_args(0), domains=doms)]
    if mesh is not None:
        from eth_consensus_specs_tpu.parallel import mesh_ops

        shards = mesh_ops.shard_count(mesh)
        out.append(
            Variant(
                "mesh",
                pd._miller_sharded_fn(mesh, 1),
                chunk_args(shards),
                mesh=mesh,
                domains=doms,
            )
        )
    return out


def synthetic_state_root_meta(n: int = 64, extra_static: int = 0):
    """A StateRootMeta with every dynamic slot the altair+ impl resolves,
    without building a spec/object state. ``extra_static`` grows the
    top-level container (and so top_depth) — the key grid uses it to
    prove the compile key discriminates container shapes."""
    from eth_consensus_specs_tpu.ops.state_root import StateRootMeta

    dynamic = (
        "validators",
        "balances",
        "inactivity_scores",
        "previous_epoch_participation",
        "current_epoch_participation",
        "justification_bits",
        "previous_justified_checkpoint",
        "current_justified_checkpoint",
        "finalized_checkpoint",
    )
    n_fields = len(dynamic) + 16 + extra_static
    top_depth = max(n_fields - 1, 0).bit_length()
    return StateRootMeta(
        dynamic_slots=tuple(enumerate(dynamic)),
        n_validators=n,
        top_depth=top_depth,
    )


def _state_root_args(meta):
    from eth_consensus_specs_tpu.ops.state_root import StateRootArrays
    from eth_consensus_specs_tpu.ops.state_columns import JustificationState

    n = meta.n_validators
    arrays = StateRootArrays(
        val_node_a=_sds((n, 8), "uint32"),
        val_node_f=_sds((n, 8), "uint32"),
        slashed_chunk=_sds((n, 8), "uint32"),
        prev_part_flags=_sds((n,), "uint8"),
        top_chunks=_sds((1 << meta.top_depth, 8), "uint32"),
        zerohashes=_sds((42, 8), "uint32"),  # zerohash_words(41): depths 0..41
    )
    just = JustificationState(
        current_epoch=_sds((), "uint64"),
        justification_bits=_sds((4,), "bool_"),
        prev_justified_epoch=_sds((), "uint64"),
        prev_justified_root=_sds((32,), "uint8"),
        cur_justified_epoch=_sds((), "uint64"),
        cur_justified_root=_sds((32,), "uint8"),
        finalized_epoch=_sds((), "uint64"),
        finalized_root=_sds((32,), "uint8"),
        block_root_prev=_sds((32,), "uint8"),
        block_root_cur=_sds((32,), "uint8"),
        slashings_sum=_sds((), "uint64"),
    )
    cols = (_sds((n,), "uint64"), _sds((n,), "uint64"), _sds((n,), "uint64"))
    return arrays, cols, just


_U64_FULL = Domain(
    "u64 SSZ value (full lane)",
    hi=(1 << 64) - 1,
    corners=(("zero", 0), ("max", (1 << 64) - 1)),
)
_BYTES_FULL = Domain(
    "opaque bytes (full u8 lane)",
    hi=255,
    corners=(("zero", 0), ("max", 255)),
)
_BOOL_DOMAIN = Domain("bit", hi=1, corners=(("false", 0), ("true", 1)))


def _state_root_domains() -> tuple:
    """One Domain per flat leaf of (arrays, bal, eff, inact, just) — the
    kernel only HASHES these (byte-swap + sha256 wraps), so every leaf's
    domain is its full lane; a future arithmetic epoch-accounting step
    would have to tighten these to survive rangelint."""
    return (
        # StateRootArrays: val_node_a, val_node_f, slashed_chunk,
        # prev_part_flags, top_chunks, zerohashes
        _WORDS32,
        _WORDS32,
        _WORDS32,
        _BYTES_FULL,
        _WORDS32,
        _WORDS32,
        # balances / effective_balance / inactivity_scores columns
        _U64_FULL,
        _U64_FULL,
        _U64_FULL,
        # JustificationState: current_epoch, justification_bits,
        # prev_justified_epoch, prev_justified_root, cur_justified_epoch,
        # cur_justified_root, finalized_epoch, finalized_root,
        # block_root_prev, block_root_cur, slashings_sum
        _U64_FULL,
        _BOOL_DOMAIN,
        _U64_FULL,
        _BYTES_FULL,
        _U64_FULL,
        _BYTES_FULL,
        _U64_FULL,
        _BYTES_FULL,
        _BYTES_FULL,
        _BYTES_FULL,
        _U64_FULL,
    )


def _state_root_variants(mesh):
    from eth_consensus_specs_tpu.ops import state_root as sr

    meta = synthetic_state_root_meta(64)
    arrays, (bal, eff, inact), just = _state_root_args(meta)

    def run(arrays, balances, effective_balance, inactivity_scores, just):
        return sr._post_epoch_state_root_impl(
            arrays, meta, balances, effective_balance, inactivity_scores, just
        )

    return [
        Variant(
            "single",
            run,
            (arrays, bal, eff, inact, just),
            domains=_state_root_domains(),
        )
    ]


def _state_root_key_grid(mesh):
    """LIVE ops/state_root.state_root_compile_key over registry shapes
    vs the flattened input avals the graph traces under."""
    from eth_consensus_specs_tpu.ops.state_root import state_root_compile_key

    out = []
    for n in (64, 128, 256):
        for extra in (0, 40):  # two container widths -> two top_depths
            meta = synthetic_state_root_meta(n, extra_static=extra)
            key = state_root_compile_key(meta)
            sig = (
                _canon_args(_state_root_args(meta)),
                meta.top_depth,
                meta.dynamic_slots,
            )
            out.append((key, sig))
    return out


def _resident_scrub_shapes(shards: int, depth: int, sub_depth: int, k: int):
    m = (1 << (depth + 1)) - 1
    return (
        _sds((shards, m, 8), "uint32"),
        _sds((k,), "int32"),
        _sds((k,), "int32"),
    )


def _resident_scrub_domains(shards: int, depth: int, sub_depth: int):
    per_shard = 1 << (depth - sub_depth)
    return (
        _WORDS32,
        Domain(
            "shard index in [0, shards)",
            hi=shards - 1,
            corners=(("zero", 0), ("last", shards - 1)),
        ),
        Domain(
            "subtree position in [0, per_shard)",
            hi=per_shard - 1,
            corners=(("zero", 0), ("last", per_shard - 1)),
        ),
    )


def _resident_scrub_variants(mesh):
    from eth_consensus_specs_tpu.ops import snapshot

    depth, sub_depth, k = 10, snapshot.SCRUB_SUBTREE_DEPTH, 4
    m = (1 << (depth + 1)) - 1
    return [
        Variant(
            "single",
            snapshot._scrub_kernel(m, sub_depth, k),
            _resident_scrub_shapes(1, depth, sub_depth, k),
            domains=_resident_scrub_domains(1, depth, sub_depth),
        )
    ]


def _resident_scrub_key_grid(mesh):
    """LIVE first_dispatch key of ops/snapshot.scrub_forest —
    ("resident_scrub", shards, n_nodes, sub_depth, k) — over registry
    shapes vs the traced (nodes, sidx, pos) signature."""
    from eth_consensus_specs_tpu.ops import snapshot

    out = []
    for depth in (8, 10):
        sd = min(snapshot.SCRUB_SUBTREE_DEPTH, depth)
        m = (1 << (depth + 1)) - 1
        for k in (4, 8):
            kk = min(k, 1 << (depth - sd))
            key = ("resident_scrub", 1, m, sd, kk)
            sig = (_canon_args(_resident_scrub_shapes(1, depth, sd, kk)), sd, kk)
            out.append((key, sig))
    return out


def _forest_args(plan):
    """ShapeDtypeStruct pytree of a StateForest under this plan — the
    donated argument of the slot_apply family (run_epochs shares the
    same layout)."""
    from eth_consensus_specs_tpu.ops.state_root import StateForest

    mv = (1 << (plan.depth_val + 1)) - 1
    mb = (1 << (plan.depth_bal + 1)) - 1
    return StateForest(
        val_nodes=_sds((plan.shards, mv, 8), "uint32"),
        bal_nodes=_sds((plan.shards, mb, 8), "uint32"),
        inact_nodes=_sds((plan.shards, mb, 8), "uint32") if plan.has_inact else None,
        part_root=_sds((8,), "uint32"),
    )


def _slot_apply_args(meta, plan, p_flags: int, p_rewards: int):
    arrays, (bal, eff, inact), just = _state_root_args(meta)
    n = meta.n_validators
    return (
        arrays,
        _forest_args(plan),
        bal,
        eff,
        inact,
        _sds((n,), "uint8"),  # prev_flags participation column
        _sds((n,), "bool_"),  # cur_tgt_att column
        just,
        _sds((p_flags,), "int32"),  # flag scatter indices (pad lanes -> 0)
        _sds((p_flags,), "uint8"),  # flag_on hit bits (pad lanes -> 0)
        _sds((p_rewards,), "int32"),  # reward scatter indices
        _sds((p_rewards,), "uint64"),  # reward amounts (pad lanes -> 0)
    )


_BALANCE_GWEI = Domain(
    "balance gwei < 2^63 (headroom for the slot's reward adds)",
    hi=(1 << 63) - 1,
    corners=(("zero", 0), ("max", (1 << 63) - 1)),
)
_REWARD_GWEI = Domain(
    "per-validator sync reward gwei < 2^32",
    hi=(1 << 32) - 1,
    corners=(("zero", 0), ("max", (1 << 32) - 1)),
)


def _slot_apply_domains(meta, plan, p_flags: int, p_rewards: int) -> tuple:
    n = meta.n_validators
    idx = Domain(
        "validator index in [0, n)",
        hi=n - 1,
        corners=(("zero", 0), ("last", n - 1)),
    )
    forest_words = (_WORDS32,) * (4 if plan.has_inact else 3)
    return (
        # StateRootArrays (same order as the state_root family)
        _WORDS32,
        _WORDS32,
        _WORDS32,
        _BYTES_FULL,
        _WORDS32,
        _WORDS32,
        # StateForest: val_nodes, bal_nodes, [inact_nodes,] part_root
        *forest_words,
        # balance is ADDED to (bounded), eff/inact are only hashed
        _BALANCE_GWEI,
        _U64_FULL,
        _U64_FULL,
        _BYTES_FULL,  # prev_flags participation byte
        _BOOL_DOMAIN,  # cur_tgt_att
        # JustificationState (same 11 as the state_root family)
        _U64_FULL,
        _BOOL_DOMAIN,
        _U64_FULL,
        _BYTES_FULL,
        _U64_FULL,
        _BYTES_FULL,
        _U64_FULL,
        _BYTES_FULL,
        _BYTES_FULL,
        _BYTES_FULL,
        _U64_FULL,
        # scatter plan lanes
        idx,
        _BOOL_DOMAIN,  # flag_on hit bit (uint8 {0, 1})
        idx,
        _REWARD_GWEI,
    )


def _slot_apply_variants(mesh):
    from eth_consensus_specs_tpu.ops import slot_pipeline
    from eth_consensus_specs_tpu.ops.state_root import forest_plan

    meta = synthetic_state_root_meta(64)
    plan = forest_plan(meta)
    p_flags, p_rewards = 8, 8
    return [
        Variant(
            "single",
            slot_pipeline._compiled_slot_apply(meta, plan, None, p_flags, p_rewards),
            _slot_apply_args(meta, plan, p_flags, p_rewards),
            domains=_slot_apply_domains(meta, plan, p_flags, p_rewards),
        )
    ]


def _slot_apply_key_grid(mesh):
    """LIVE serve/buckets.slot_key over the request-capacity grid
    (registry size x flag/reward capacities — capacities are derived
    from the request ALONE, so the router and the dispatch share this
    exact surface) vs the flat traced arg shapes the jit caches on."""
    from eth_consensus_specs_tpu.ops.state_root import forest_plan
    from eth_consensus_specs_tpu.serve import buckets

    out = []
    for n in (64, 128):
        meta = synthetic_state_root_meta(n)
        plan = forest_plan(meta)
        for flags in (1, 5, 8, 64):
            for rewards in (1, 16):
                key = buckets.slot_key(n, flags, rewards, plan)
                args = _slot_apply_args(meta, plan, key[2], key[3])
                sig = (_canon_args(args), tuple(plan))
                out.append((key, sig))
    return out


def _canon_args(args) -> tuple:
    """Canonical hashable form of a ShapeDtypeStruct pytree — the part
    of the jit cache key the shape grid varies."""
    import jax

    return tuple(
        (tuple(leaf.shape), str(leaf.dtype)) for leaf in jax.tree_util.tree_leaves(args)
    )


# ------------------------------------------------------------- registry --

_LIMB_DTYPES = frozenset({"uint64", "uint32", "int32", "bool"})

_MASK30 = (1 << 30) - 1  # field_limbs / limb_field limb mask
_MASK26 = (1 << 26) - 1  # lazy_limbs limb mask

REGISTRY: tuple[KernelSpec, ...] = (
    KernelSpec(
        name="sha256",
        help="tiled vectorized SHA-256 (ops/sha256.sha256_tiled)",
        dtypes=frozenset({"uint32"}),
        donation_waiver="message (N,16) and digest (N,8) avals never alias; "
        "tiles are transient host uploads reused across levels",
        wraps=_SHA_WRAPS,
        build_variants=_sha256_variants,
    ),
    KernelSpec(
        name="merkle",
        help="single-subtree device merkleization (ops/merkle)",
        # bool: the fori_loop predicate scalar; int32: its counter
        dtypes=frozenset({"uint32", "int32", "bool"}),
        donation_waiver="leaf buffer (2^d,8) vs root (8,) never alias; the "
        "resident-state seam (ROADMAP item 2) donates at the column level, "
        "not here",
        wraps=_SHA_WRAPS,
        build_variants=_merkle_variants,
    ),
    KernelSpec(
        name="merkle_many",
        help="vmapped multi-tree merkleization, mesh tree-axis sharded",
        dtypes=frozenset({"uint32", "int32", "bool"}),
        donation_waiver="batched leaves (B,2^d,8) vs roots (B,8) never alias",
        wraps=_SHA_WRAPS,
        build_variants=_merkle_many_variants,
        key_grid=_merkle_many_key_grid,
    ),
    KernelSpec(
        name="merkle_inc",
        help="incremental dirty-subtree forest update (ops/merkle_inc), "
        "mesh leaf-axis sharded",
        dtypes=frozenset({"uint32", "int32", "bool"}),
        # the forest node buffer: every epoch's update lands in place —
        # this donation IS the resident-footprint claim the ROADMAP
        # item-1 rework makes, proven per kernel by the audit
        donate=(0,),
        wraps=_SHA_WRAPS,
        build_variants=_merkle_inc_variants,
        key_grid=_merkle_inc_key_grid,
    ),
    KernelSpec(
        name="shuffle",
        help="whole-permutation swap-or-not shuffle (ops/shuffle)",
        dtypes=frozenset({"uint32", "int32", "bool"}),
        donation_waiver="seed words and pivots are read-only; the padded list is "
        "the caller's host array, and the loop carries its own copy",
        wraps=_SHA_WRAPS,
        build_variants=_shuffle_variants,
    ),
    KernelSpec(
        name="fr_fft",
        help="batched BLS-scalar-field FFT (ops/fr_fft), mesh "
        "batch-axis sharded",
        dtypes=_LIMB_DTYPES,
        donate=(0,),  # vals: private bit-reversed copy, aval == output
        wraps=limb_borrow_wraps("limb_field.py", _MASK30),
        build_variants=_fr_fft_variants,
        key_grid=_fr_fft_key_grid,
    ),
    KernelSpec(
        name="g1_msm",
        help="G1 multi-scalar multiplication, mesh lane-axis sharded",
        dtypes=_LIMB_DTYPES,
        donation_waiver="lane arrays (N,13)x3 + bits (N,256) vs one Jacobian "
        "point (13,)x3 — no aval ever aliases an output",
        wraps=lazy_lend_wraps(),
        build_variants=_g1_msm_variants,
    ),
    KernelSpec(
        name="bls_msm",
        help="batched per-item G1 committee sums (the serve RLC seam), "
        "mesh item-axis sharded",
        dtypes=_LIMB_DTYPES,
        donation_waiver="committee lanes (I,L,13)x3 vs per-item points "
        "(I,13)x3 — shapes never alias",
        wraps=lazy_lend_wraps(),
        build_variants=_bls_msm_variants,
        key_grid=_bls_msm_key_grid,
    ),
    KernelSpec(
        name="kzg_msm",
        help="batched per-item full-scalar G1 MSMs (the KZG blob RLC "
        "fold — ops/g1_msm.msm_many_kernel), mesh lane-axis sharded",
        dtypes=_LIMB_DTYPES,
        donation_waiver="MSM lanes (I,L,13)x3 + bits (I,L,256) vs "
        "per-item Jacobian points (I,13)x3 — no aval ever aliases an "
        "output",
        wraps=lazy_lend_wraps(),
        build_variants=_kzg_msm_variants,
        key_grid=_kzg_msm_key_grid,
    ),
    KernelSpec(
        name="g2_aggregate",
        help="batched ragged-committee G2 signature sums (the aggregation "
        "pipeline seam), mesh lane-axis sharded",
        dtypes=_LIMB_DTYPES,
        donation_waiver="committee lanes (I,L,2,15)x3 vs per-item Jacobian "
        "points (I,2,15)x3 — shapes never alias",
        wraps=lazy_lend_wraps(),
        build_variants=_g2_agg_variants,
        key_grid=_g2_agg_key_grid,
    ),
    KernelSpec(
        name="pairing",
        help="chunked Miller accumulation + fold, mesh chunk-axis sharded",
        dtypes=frozenset({"uint64", "uint32", "uint8", "int32", "bool"}),
        donation_waiver="prepared coefficients are cached host constants "
        "(_PREP_CACHE) reused across batches — donating them would corrupt "
        "the cache",
        wraps=lazy_lend_wraps(),
        build_variants=_pairing_variants,
    ),
    KernelSpec(
        name="state_root",
        help="post-accounting-epoch BeaconState root (ops/state_root)",
        dtypes=frozenset({"uint32", "uint64", "uint8", "int32", "bool"}),
        donation_waiver="static tree arrays are reused every epoch "
        "(device-resident by design); donation lands with the in-place "
        "per-slot updates of ROADMAP item 2",
        wraps=_SHA_WRAPS,
        build_variants=_state_root_variants,
        key_grid=_state_root_key_grid,
    ),
    KernelSpec(
        name="resident_scrub",
        help="salted-subtree resident forest integrity scrub "
        "(ops/snapshot._scrub_kernel): K subtrees re-hashed from their "
        "resident leaves + the full upper region, compared against the "
        "stored rows",
        dtypes=frozenset({"uint32", "int32", "bool"}),
        donation_waiver="read-only verification pass: the resident node "
        "buffer must SURVIVE the scrub (a donated forest could not be "
        "quarantine-rebuilt from its own leaves afterwards)",
        wraps=_SHA_WRAPS,
        build_variants=_resident_scrub_variants,
        key_grid=_resident_scrub_key_grid,
    ),
    KernelSpec(
        name="slot_apply",
        help="whole-slot fused apply (ops/slot_pipeline._compiled_slot_apply): "
        "duplicate-safe participation scatter + sync-reward balance adds + "
        "incremental re-root against the resident forest, one donated dispatch",
        dtypes=frozenset({"uint32", "uint64", "uint8", "int32", "bool"}),
        # the resident forest (flat invars 6..9 after the 6 StateRootArrays
        # leaves): slot N+1 updates slot N's tree levels in place — the
        # run_epochs lifecycle, same buffers
        donate=(6, 7, 8, 9),
        wraps=_SHA_WRAPS,
        build_variants=_slot_apply_variants,
        key_grid=_slot_apply_key_grid,
    ),
)

for _spec in REGISTRY:
    if not _spec.donate and not _spec.donation_waiver:
        raise AssertionError(
            f"kernel registry entry {_spec.name!r} declares neither donated "
            "argnums nor a donation waiver — silence is not a donation policy"
        )


def by_name() -> dict[str, KernelSpec]:
    return {s.name: s for s in REGISTRY}


def mesh_families(mesh) -> set[str]:
    """Families whose builders emit a mesh variant on this mesh —
    derived from the builders themselves (the authoritative source),
    not a hand-maintained list."""
    if mesh is None:
        return set()
    return {
        s.name
        for s in REGISTRY
        if any(v.mesh is not None for v in s.build_variants(mesh))
    }
