"""Full BeaconState merkleization on device with dirty-path rehash.

SURVEY hard part 3: at 1M validators the reference's (cached) behavior —
full-state rehash per slot through remerkleable — is the top cost of
`state_transition` (reference: specs/phase0/beacon-chain.md:1383-1393 via
utils/hash_function.py). This module keeps the STATE TREE's big regions
device-resident and re-hashes only the paths the accounting epoch
actually dirties:

* per-validator subtrees: of the 8 Validator fields only
  effective_balance changes during accounting, so the static 2/3 of each
  validator's 15-node tree (pubkey root + withdrawal_credentials node;
  the four epoch fields' node) is precomputed ONCE at ingest via the
  native C sha core, and each epoch recomputes just 3 hashes/validator
  on device (B = H(eff_balance, slashed), E = H(A, B), root = H(E, F));
* the big flat columns (balances, inactivity scores, participation) are
  chunked and tree-reduced on device (ops/merkle.tree_root_words), then
  zero-hash-folded to their SSZ limit depth and length-mixed;
* every OTHER state field's root is harvested once at ingest from the
  object tree's cached roots and sits as a static chunk; the top-level
  container combine (~32 chunks) runs on device each epoch.

The result is `hash_tree_root(state)` for the post-accounting state as
PURE device work after one ingest — the north-star shape (BASELINE.json:
epoch-boundary state_transition incl. full state root < 1s @ 1M).

Bit-exactness: tests/test_state_root_device.py compares against
ssz.hash_tree_root on the equivalently-updated object state.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Iterable, Mapping, NamedTuple

import numpy as np

import eth_consensus_specs_tpu  # noqa: F401
import jax
import jax.numpy as jnp
from jax import lax

from eth_consensus_specs_tpu import fault, obs
from eth_consensus_specs_tpu.obs import waterfall
from eth_consensus_specs_tpu.ops.merkle import tree_root_words
from eth_consensus_specs_tpu.ops.sha256 import (
    sha256_pair_words,
    sha256_pair_words_scan,
    sha256_pair_words_unrolled,
)

VALIDATOR_REGISTRY_LIMIT_LOG2 = 40  # List[Validator, 2**40]
BALANCE_LIMIT_CHUNKS_LOG2 = 38  # 2**40 u64 -> 2**38 chunks
PARTICIPATION_LIMIT_CHUNKS_LOG2 = 35  # 2**40 bytes -> 2**35 chunks


def _bytes_to_words(b: bytes) -> np.ndarray:
    return np.frombuffer(b, dtype=">u4").astype(np.uint32)


def zerohash_words(max_depth: int) -> np.ndarray:
    """[max_depth+1, 8] u32 — zerohashes[d] as BE words."""
    from eth_consensus_specs_tpu.ssz.merkle import zerohashes

    return np.stack([_bytes_to_words(zerohashes[d]) for d in range(max_depth + 1)])


class StateRootArrays(NamedTuple):
    """Device-resident static tree content (a pure-array pytree, safe to
    pass through jit)."""

    val_node_a: jnp.ndarray  # u32[N, 8]  H(pubkey_root, withdrawal_credentials)
    val_node_f: jnp.ndarray  # u32[N, 8]  H(H(aee, ae), H(exit, withdrawable))
    slashed_chunk: jnp.ndarray  # u32[N, 8] SSZ chunk of `slashed`
    prev_part_flags: jnp.ndarray  # u8[N] participation bytes rotated into prev
    top_chunks: jnp.ndarray  # u32[P, 8] all field roots (static slots filled)
    zerohashes: jnp.ndarray  # u32[41, 8]


class StateRootMeta(NamedTuple):
    """Hashable host-side layout data (closure/static side of the jit)."""

    dynamic_slots: tuple  # ((field index, field name), ...)
    n_validators: int
    top_depth: int


def _u64_chunk_words(vals: jnp.ndarray) -> jnp.ndarray:
    """u64[N] -> SSZ 32-byte chunks as u32[N, 8] BE words (value LE in the
    first 8 bytes of the chunk)."""
    lo = (vals & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    hi = (vals >> jnp.uint64(32)).astype(jnp.uint32)

    def bswap(w):
        return (
            ((w & jnp.uint32(0xFF)) << 24)
            | ((w & jnp.uint32(0xFF00)) << 8)
            | ((w >> 8) & jnp.uint32(0xFF00))
            | ((w >> 24) & jnp.uint32(0xFF))
        )

    z = jnp.zeros_like(lo)
    return jnp.stack([bswap(lo), bswap(hi), z, z, z, z, z, z], axis=-1)


def _hash_rows(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """H(a || b) rowwise for u32[..., 8] word chunks."""
    return sha256_pair_words(jnp.concatenate([a, b], axis=-1))


def packed_u64_leaves(vals: jnp.ndarray, n: int) -> jnp.ndarray:
    """u64[n] (n % 4 == 0) -> u32[n//4, 8] SSZ packed chunk words."""
    w = lax.bitcast_convert_type(vals, jnp.uint32).reshape(n // 4, 8)
    return (
        ((w & 0xFF) << 24)
        | ((w & 0xFF00) << 8)
        | ((w >> 8) & 0xFF00)
        | ((w >> 24) & 0xFF)
    )


def packed_u8_leaves(vals: jnp.ndarray, n: int) -> jnp.ndarray:
    """u8[n] (n % 32 == 0) -> u32[n//32, 8] SSZ packed chunk words."""
    w = vals.reshape(n // 32, 8, 4).astype(jnp.uint32)
    return (w[..., 0] << 24) | (w[..., 1] << 16) | (w[..., 2] << 8) | w[..., 3]


class ListTail(NamedTuple):
    """What an SSZ list's root still needs above its live subtree: the
    zero-hash siblings up to the limit depth, then the length."""

    root: jnp.ndarray  # u32[8] root of the subtree over the live chunks
    depth: int  # that subtree's depth
    limit_log2: int  # depth of the list's limit, in chunks
    length: int  # element count, mixed in last


def _tree_depth(leaves: int) -> int:
    return max(leaves - 1, 0).bit_length()


def list_tail_steps(tails: Iterable[ListTail]) -> int:
    """Sequential hash steps :func:`list_roots` runs for these tails: the
    longest zero-hash chain, and the one step that mixes every length."""
    return max(max(t.limit_log2 - t.depth, 0) for t in tails) + 1


def _chain_hash(words: jnp.ndarray) -> jnp.ndarray:
    """The hash of a chain step. On an accelerator the UNROLLED body
    whatever the batch, the one small-batch caller that takes it: a
    chain's body compiles once and runs some twenty times one after the
    other, where the round scan that `sha256.SMALL_BATCH` sends every
    other small hash through is 128 loop trips a hash (1.56 ms against a
    whole tile's 0.08 on a v5e; PERF.md, PR 26) and four such chains
    were 61 % of a 2^20 root. XLA:CPU keeps the round scan, as every sha
    call does there."""
    if jax.default_backend() == "cpu":
        return sha256_pair_words_scan(words)
    return sha256_pair_words_unrolled(words)


def list_roots(tails: Mapping, zh: jnp.ndarray) -> dict:
    """Finish several list roots at once, a root under each key of
    `tails`: chain each subtree root up to its limit depth with
    zero-hash siblings (right sibling = zerohashes[d] at level d), then
    mix in its length. The chains are independent, and equally long for
    every registry of 17 validators or more, so they ride ONE scan as
    lanes: step s hashes, for every lane, its running root beside the
    zero hash of its own level, the last step its root beside its length
    chunk. A shorter chain enters at its own start step and is passed
    through before it. One sha body a program (a python loop put ~25 PER
    CHAIN into the jaxpr, the bulk of the compile wall) and one chain's
    steps a root (a scan a list ran the lists one after another). This
    is the only fold. On a v5e two to four lanes, 16 and 128 run a chain
    of 21 steps in 0.05-0.06 ms; five or eight take 0.25 ms and ~35 s of
    compile, one lane 1.04 ms (PERF.md, PR 28): no program has five."""
    lanes = list(tails.values())
    steps = list_tail_steps(lanes)
    level = np.zeros((steps - 1, len(lanes)), np.int32)
    live = np.ones((steps, len(lanes)), bool)
    for lane, t in enumerate(lanes):
        start = steps - 1 - max(t.limit_log2 - t.depth, 0)
        live[:start, lane] = False
        level[start:, lane] = np.arange(t.depth, max(t.limit_log2, t.depth))
    lengths = np.stack(
        [_bytes_to_words(int(t.length).to_bytes(8, "little") + bytes(24)) for t in lanes]
    )
    rights = jnp.concatenate([zh[level], lengths[None]])  # [steps, lanes, 8]
    ragged = not live.all()

    def step(roots, operands):
        right, on = operands
        out = _chain_hash(jnp.concatenate([roots, right], axis=-1))
        if ragged:
            out = jnp.where(on[:, None], out, roots)
        return out, None

    roots, _ = lax.scan(step, jnp.stack([t.root for t in lanes]), (rights, live))
    return dict(zip(tails, roots))


def _validator_leaf_rows(
    effective_balance: jnp.ndarray,
    slashed_chunk: jnp.ndarray,
    node_a: jnp.ndarray,
    node_f: jnp.ndarray,
) -> jnp.ndarray:
    """The per-validator root from its static nodes + the dynamic
    effective balance — the 3-hash chain (B = H(eff_chunk, slashed),
    E = H(A, B), root = H(E, F)). ONE implementation: the full path
    applies it to whole columns, the incremental path to the gathered
    dirty rows — editing the Validator leaf derivation in one place
    cannot break full-vs-incremental root parity.

    The chain runs as ONE compression body in a three-step scan (step i
    hashes its two operands, each either a column or the step before's
    digest), not three bodies: compiled for a v5e a body at these widths
    is ~10 s, and the leaf sits in four programs of the served slot,
    twice in two of them (PERF.md, PR 22)."""
    eb_chunk = _u64_chunk_words(effective_balance)
    zero = jnp.zeros_like(eb_chunk)
    lefts = jnp.stack([eb_chunk, node_a, zero])
    rights = jnp.stack([slashed_chunk, zero, node_f])
    # where the running digest goes: nowhere, right (H(A, B)), left (H(E, F))
    carry_left = jnp.asarray([False, False, True])
    carry_right = jnp.asarray([False, True, False])

    def step(digest, operands):
        left, right, use_left, use_right = operands
        digest = _hash_rows(
            jnp.where(use_left, digest, left), jnp.where(use_right, digest, right)
        )
        return digest, None

    root, _ = lax.scan(step, zero, (lefts, rights, carry_left, carry_right))
    return root


def validator_registry_tail(
    arrays: StateRootArrays, n: int, effective_balance: jnp.ndarray
) -> ListTail:
    """List[Validator] subtree from the static nodes + the dynamic
    effective-balance column: 3 hashes per validator + the leaf tree."""
    roots = _validator_leaf_rows(
        effective_balance, arrays.slashed_chunk, arrays.val_node_a, arrays.val_node_f
    )  # [N, 8] validator roots
    depth = _tree_depth(n)
    sub = tree_root_words(_pad_pow2(roots, depth), depth)
    return ListTail(sub, depth, VALIDATOR_REGISTRY_LIMIT_LOG2, n)


def _pad_pow2(leaves: jnp.ndarray, depth: int) -> jnp.ndarray:
    pad = (1 << depth) - leaves.shape[0]
    if pad:
        leaves = jnp.concatenate([leaves, jnp.zeros((pad, 8), jnp.uint32)], axis=0)
    return leaves


def u64_list_tail(vals: jnp.ndarray, n: int, limit_chunks_log2: int) -> ListTail:
    if n % 4:
        vals = jnp.concatenate([vals, jnp.zeros(4 - n % 4, jnp.uint64)])
    leaves = packed_u64_leaves(vals, vals.shape[0])
    depth = _tree_depth((n + 3) // 4)
    sub = tree_root_words(_pad_pow2(leaves, depth), depth)
    return ListTail(sub, depth, limit_chunks_log2, n)


def u8_list_tail(vals: jnp.ndarray, n: int, limit_chunks_log2: int) -> ListTail:
    if n % 32:
        vals = jnp.concatenate([vals, jnp.zeros(32 - n % 32, jnp.uint8)])
    leaves = packed_u8_leaves(vals, vals.shape[0])
    depth = _tree_depth((n + 31) // 32)
    sub = tree_root_words(_pad_pow2(leaves, depth), depth)
    return ListTail(sub, depth, limit_chunks_log2, n)


def _zero_u8_list_root_words(n: int) -> np.ndarray:
    """Host-computed root words of an all-zero List[uint8-ish, 2**40] of
    length n (the rotated-in current participation): zero subtree =
    zerohashes[depth], folded to the limit depth, length-mixed."""
    from eth_consensus_specs_tpu.ssz.hashing import hash_bytes
    from eth_consensus_specs_tpu.ssz.merkle import zerohashes

    chunks = (n + 31) // 32
    depth = max(chunks - 1, 0).bit_length() if n else 0
    root = zerohashes[depth]
    for d in range(depth, PARTICIPATION_LIMIT_CHUNKS_LOG2):
        root = hash_bytes(root + zerohashes[d])
    root = hash_bytes(root + int(n).to_bytes(8, "little") + b"\x00" * 24)
    return _bytes_to_words(root)


def checkpoint_root(epoch: jnp.ndarray, root_bytes: jnp.ndarray) -> jnp.ndarray:
    """Checkpoint container root: H(chunk(epoch), root). `root_bytes` is
    u8[32]."""
    e_chunk = _u64_chunk_words(epoch.reshape(1).astype(jnp.uint64))[0]
    r_words = root_bytes.reshape(8, 4).astype(jnp.uint32)
    r_chunk = (
        (r_words[:, 0] << 24) | (r_words[:, 1] << 16) | (r_words[:, 2] << 8) | r_words[:, 3]
    )
    return _hash_rows(e_chunk[None, :], r_chunk[None, :])[0]


def bitvector4_chunk(bits: jnp.ndarray) -> jnp.ndarray:
    """Bitvector[4] (bool[4]) -> its single SSZ chunk as u32[8] words."""
    byte = (
        bits[0].astype(jnp.uint32)
        | (bits[1].astype(jnp.uint32) << 1)
        | (bits[2].astype(jnp.uint32) << 2)
        | (bits[3].astype(jnp.uint32) << 3)
    )
    chunk = jnp.zeros(8, jnp.uint32)
    return chunk.at[0].set(byte << 24)


def combine_state_root(
    arrays: StateRootArrays, meta: StateRootMeta, dynamic_roots: dict[int, jnp.ndarray]
) -> jnp.ndarray:
    """Write the dynamic roots into their top-level slots and reduce the
    container tree on device."""
    chunks = arrays.top_chunks
    for slot, root in dynamic_roots.items():
        chunks = chunks.at[slot].set(root)
    return tree_root_words(chunks, meta.top_depth)


# ------------------------------------------------------------------ ingest --


def build_static(
    spec, state, prev_part_from_current: bool = True
) -> tuple[StateRootArrays, StateRootMeta]:
    """Harvest the static tree content from an object state (one-time,
    host; per-validator static nodes go through the native C sha core)."""
    import jax

    from eth_consensus_specs_tpu import ssz
    from eth_consensus_specs_tpu.ssz.hashing import hash_bytes
    from eth_consensus_specs_tpu.native import available as native_available, sha256_pairs

    n = len(state.validators)

    def pair_hash_many(data: bytes) -> bytes:
        if native_available():
            return sha256_pairs(data)
        out = []
        for i in range(0, len(data), 64):
            out.append(hash_bytes(data[i : i + 64]))
        return b"".join(out)

    # pubkey roots: H(pk[0:32], pk[32:48] || zeros)
    pk_stream = b"".join(
        bytes(v.pubkey)[:32] + bytes(v.pubkey)[32:48] + b"\x00" * 16
        for v in state.validators
    )
    pk_roots = pair_hash_many(pk_stream)
    # A = H(pubkey_root, withdrawal_credentials)
    a_stream = b"".join(
        pk_roots[i * 32 : (i + 1) * 32] + bytes(v.withdrawal_credentials)
        for i, v in enumerate(state.validators)
    )
    node_a = pair_hash_many(a_stream)

    def epoch_chunk(e: int) -> bytes:
        return int(e).to_bytes(8, "little") + b"\x00" * 24

    c_stream = b"".join(
        epoch_chunk(v.activation_eligibility_epoch) + epoch_chunk(v.activation_epoch)
        for v in state.validators
    )
    d_stream = b"".join(
        epoch_chunk(v.exit_epoch) + epoch_chunk(v.withdrawable_epoch)
        for v in state.validators
    )
    node_c = pair_hash_many(c_stream)
    node_d = pair_hash_many(d_stream)
    f_stream = b"".join(
        node_c[i * 32 : (i + 1) * 32] + node_d[i * 32 : (i + 1) * 32] for i in range(n)
    )
    node_f = pair_hash_many(f_stream)

    slashed_chunks = np.zeros((n, 8), np.uint32)
    for i, v in enumerate(state.validators):
        if v.slashed:
            slashed_chunks[i, 0] = 0x01000000

    fields = list(type(state).fields())
    top_depth = max(len(fields) - 1, 0).bit_length()
    top_chunks = np.zeros((1 << top_depth, 8), np.uint32)
    dynamic_names = {
        "validators",
        "balances",
        "inactivity_scores",
        "previous_epoch_participation",
        "current_epoch_participation",
        "justification_bits",
        "previous_justified_checkpoint",
        "current_justified_checkpoint",
        "finalized_checkpoint",
    }
    dynamic_slots = []
    for i, name in enumerate(fields):
        if name in dynamic_names:
            dynamic_slots.append((i, name))
        else:
            top_chunks[i] = _bytes_to_words(bytes(ssz.hash_tree_root(getattr(state, name))))

    prev_flags = np.array(
        [int(b) for b in state.current_epoch_participation]
        if prev_part_from_current
        else [int(b) for b in state.previous_epoch_participation],
        np.uint8,
    )

    def words(b: bytes, rows: int) -> np.ndarray:
        return np.frombuffer(b, dtype=">u4").astype(np.uint32).reshape(rows, 8)

    arrays = StateRootArrays(
        val_node_a=jax.device_put(jnp.asarray(words(node_a, n))),
        val_node_f=jax.device_put(jnp.asarray(words(node_f, n))),
        slashed_chunk=jax.device_put(jnp.asarray(slashed_chunks)),
        prev_part_flags=jax.device_put(jnp.asarray(prev_flags)),
        top_chunks=jax.device_put(jnp.asarray(top_chunks)),
        zerohashes=jax.device_put(jnp.asarray(zerohash_words(41))),
    )
    try:
        from eth_consensus_specs_tpu.obs import ledger

        ledger.register(
            "resident_state",
            f"static_tree-{n}",
            sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(arrays)),
        )
    except Exception:
        pass
    meta = StateRootMeta(
        dynamic_slots=tuple(dynamic_slots), n_validators=n, top_depth=top_depth
    )
    return arrays, meta


def synthetic_meta(spec, n: int) -> StateRootMeta:
    """The StateRootMeta :func:`synthetic_static` pairs its arrays with —
    the container shape of ``spec.BeaconState`` at registry size n, no
    array built (the compile rehearsals need only this)."""
    fields = list(spec.BeaconState.fields())
    top_depth = max(len(fields) - 1, 0).bit_length()
    dynamic_names = {
        "validators",
        "balances",
        "inactivity_scores",
        "previous_epoch_participation",
        "current_epoch_participation",
        "justification_bits",
        "previous_justified_checkpoint",
        "current_justified_checkpoint",
        "finalized_checkpoint",
    }
    dynamic_slots = tuple(
        (i, name) for i, name in enumerate(fields) if name in dynamic_names
    )
    return StateRootMeta(
        dynamic_slots=dynamic_slots, n_validators=n, top_depth=top_depth
    )


def synthetic_static(spec, n: int, seed: int = 0) -> tuple[StateRootArrays, StateRootMeta]:
    """Bench/demo static content WITHOUT building an n-validator object
    state: random static nodes, zero small-field chunks — the exact same
    device hash count and tree shape as build_static, minus the one-time
    host harvest. Roots are not meaningful; timings are."""
    import jax

    rng = np.random.default_rng(seed)
    meta = synthetic_meta(spec, n)
    top_depth = meta.top_depth

    def rnd(shape):
        return jax.device_put(
            jnp.asarray(rng.integers(0, 2**32, size=shape, dtype=np.uint64).astype(np.uint32))
        )

    arrays = StateRootArrays(
        val_node_a=rnd((n, 8)),
        val_node_f=rnd((n, 8)),
        slashed_chunk=jax.device_put(jnp.zeros((n, 8), jnp.uint32)),
        prev_part_flags=jax.device_put(
            jnp.asarray(rng.integers(0, 8, size=n, dtype=np.int64).astype(np.uint8))
        ),
        top_chunks=rnd((1 << top_depth, 8)),
        zerohashes=jax.device_put(jnp.asarray(zerohash_words(41))),
    )
    try:
        # creation-site HBM booking (obs/ledger.py): this static tree is
        # resident for as long as the caller keeps it — a service holds
        # it across its whole life
        from eth_consensus_specs_tpu.obs import ledger

        ledger.register(
            "resident_state",
            f"static_tree_synthetic-{n}",
            sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(arrays)),
        )
    except Exception:
        pass
    return arrays, meta


def state_root_real_hashes(meta: StateRootMeta) -> int:
    """Compressions one post_epoch_state_root evaluation executes — the
    honest work count for the span's roofline verdict: validator nodes +
    each column tree as merkle.tree_root_words walks it."""
    from eth_consensus_specs_tpu.ops.merkle import tree_real_hashes

    n = meta.n_validators
    names = {name for _, name in meta.dynamic_slots}
    hashes = 3 * n + tree_real_hashes(max(n - 1, 0).bit_length())  # validator subtrees + registry
    d_bal = (max(n // 4, 1) - 1).bit_length()
    hashes += tree_real_hashes(d_bal)  # balances
    if "inactivity_scores" in names:
        hashes += tree_real_hashes(d_bal)
    if "previous_epoch_participation" in names:
        hashes += tree_real_hashes((max(n // 32, 1) - 1).bit_length())
    return hashes + (1 << meta.top_depth)


def state_root_chain_steps(meta: StateRootMeta) -> int:
    """Sequential one-message hash steps of one post_epoch_state_root
    evaluation's list tails: the longest chain and the length mix, once,
    however many lists ride it (:func:`list_tail_steps` of the depths
    the program folds from)."""
    n = meta.n_validators
    shapes = [
        (_tree_depth(n), VALIDATOR_REGISTRY_LIMIT_LOG2),
        (_tree_depth((n + 3) // 4), BALANCE_LIMIT_CHUNKS_LOG2),  # scores: the same
    ]
    if any(name == "previous_epoch_participation" for _, name in meta.dynamic_slots):
        shapes.append((_tree_depth((n + 31) // 32), PARTICIPATION_LIMIT_CHUNKS_LOG2))
    return list_tail_steps(ListTail(None, d, limit, n) for d, limit in shapes)


def slot_root_real_hashes(n: int, top_depth: int) -> int:
    """Compressions of one per-slot dirty-path root (balances + both
    participation columns + the top tree) — the accounting behind the
    block_epoch span's ``work_bytes`` and its hash counters."""
    from eth_consensus_specs_tpu.ops.merkle import tree_real_hashes

    return (
        tree_real_hashes((max(n // 4, 1) - 1).bit_length())
        + 2 * tree_real_hashes((max(n // 32, 1) - 1).bit_length())
        + (1 << top_depth)
    )


def post_epoch_state_root(
    arrays: StateRootArrays,
    meta: StateRootMeta,
    balances: jnp.ndarray,
    effective_balance: jnp.ndarray,
    inactivity_scores: jnp.ndarray,
    just,  # JustificationState-like with post-epoch values
) -> jnp.ndarray:
    """The full post-accounting-epoch state root as one device graph."""
    if obs.tracing(balances):
        # composed under an outer jit (parallel/resident.py): the trace
        # runs once per compile — count it, but never clock it as a run
        obs.count("state_root.traces", 1)
        return _post_epoch_state_root_impl(
            arrays, meta, balances, effective_balance, inactivity_scores, just
        )
    real = state_root_real_hashes(meta)

    def _device():
        fault.check("state_root.device")
        with obs.span(
            "state_root.post_epoch", work_bytes=96 * real, n_validators=meta.n_validators
        ) as sp:
            # the call until it returns: argument transfer and enqueue
            with waterfall.leg("state_root.launch"):
                out = _compiled_state_root(meta)(
                    arrays, balances, effective_balance, inactivity_scores, just
                )
            # no body: the leg's exit blocks on the root, where the span
            # round both blocks anyway (with obs off neither does)
            with waterfall.leg("state_root.wait") as wait:
                wait.result = out
            sp.result = out
        return out

    # device-side death (compile/OOM/injected) degrades to the host
    # oracle: the run completes slower rather than not at all
    out = fault.degrade(
        "state_root.device",
        _device,
        lambda: _post_epoch_state_root_host(
            arrays, meta, balances, effective_balance, inactivity_scores, just
        ),
    )
    obs.count("state_root.roots", 1)
    obs.count("state_root.real_hashes", real)
    obs.count("state_root.chain_steps", state_root_chain_steps(meta))
    return out


@lru_cache(maxsize=None)
def _compiled_state_root(meta: StateRootMeta):
    """One executable per registry/container shape. Run eagerly the
    graph is one dispatch per primitive — on an accelerator, where the
    sha rounds are unrolled, some hundred thousand of them a root."""
    import jax

    @jax.jit
    def run(arrays, balances, effective_balance, inactivity_scores, just):
        return _post_epoch_state_root_impl(
            arrays, meta, balances, effective_balance, inactivity_scores, just
        )

    return run


def state_root_compile_key(meta: StateRootMeta) -> tuple:
    """Shape key the jitted state-root graph compiles under. The serving
    layer groups queued state-root requests by this key so every request
    for the same registry shape hits the same compiled executable, and
    counts first sightings as `serve.compiles` (serve/buckets.py)."""
    return ("state_root", meta.n_validators, meta.top_depth, len(meta.dynamic_slots))


def post_epoch_state_root_host(
    arrays: StateRootArrays,
    meta: StateRootMeta,
    balances,
    effective_balance,
    inactivity_scores,
    just,
) -> jnp.ndarray:
    """Public host-oracle entry (no XLA anywhere) — what the serving
    layer's whole-batch degradation falls back to on device death."""
    return _post_epoch_state_root_host(
        arrays, meta, balances, effective_balance, inactivity_scores, just
    )


def _post_epoch_state_root_host(
    arrays: StateRootArrays,
    meta: StateRootMeta,
    balances,
    effective_balance,
    inactivity_scores,
    just,
) -> jnp.ndarray:
    """fault.degrade fallback: the SAME tree through the host oracle's
    native-sha path (ops/state_root_host.py) — no XLA anywhere."""
    import jax

    from eth_consensus_specs_tpu.ops.state_root_host import post_epoch_state_root_np

    arrays_np = jax.tree_util.tree_map(np.asarray, arrays)
    just_np = jax.tree_util.tree_map(np.asarray, just)
    with obs.span("state_root.post_epoch_host", n_validators=meta.n_validators):
        out = post_epoch_state_root_np(
            arrays_np,
            meta,
            np.asarray(balances),
            np.asarray(effective_balance),
            np.asarray(inactivity_scores),
            just_np,
        )
    return jnp.asarray(out)


def _post_epoch_state_root_impl(
    arrays: StateRootArrays,
    meta: StateRootMeta,
    balances: jnp.ndarray,
    effective_balance: jnp.ndarray,
    inactivity_scores: jnp.ndarray,
    just,
) -> jnp.ndarray:
    n = meta.n_validators
    zh = arrays.zerohashes
    slot_of = {name: i for i, name in meta.dynamic_slots}
    dyn: dict[int, jnp.ndarray] = {}
    # every list's subtree first, then all their tails as lanes of one scan
    tails = {
        slot_of["validators"]: validator_registry_tail(arrays, n, effective_balance),
        slot_of["balances"]: u64_list_tail(balances, n, BALANCE_LIMIT_CHUNKS_LOG2),
    }
    if "inactivity_scores" in slot_of:
        tails[slot_of["inactivity_scores"]] = u64_list_tail(
            inactivity_scores, n, BALANCE_LIMIT_CHUNKS_LOG2
        )
    if "previous_epoch_participation" in slot_of:
        tails[slot_of["previous_epoch_participation"]] = u8_list_tail(
            arrays.prev_part_flags, n, PARTICIPATION_LIMIT_CHUNKS_LOG2
        )
        # rotated-in current participation: all zero, length n — a
        # CONSTANT for fixed n, folded at trace time (host hashes), not
        # recomputed as an O(n/32) device tree every epoch
        dyn[slot_of["current_epoch_participation"]] = jnp.asarray(
            _zero_u8_list_root_words(n)
        )
    dyn.update(list_roots(tails, zh))
    dyn.update(_small_dynamic_roots(slot_of, just))
    return combine_state_root(arrays, meta, dyn)


def _small_dynamic_roots(slot_of: dict, just) -> dict:
    """The O(1)-sized dynamic roots (justification bits + the three
    checkpoints) — ONE implementation shared by the full recompute and
    the incremental path, so the two can never disagree on the cheap
    fields while differing on the trees."""
    dyn = {
        slot_of["justification_bits"]: (
            bitvector4_chunk(just.justification_bits)
            if just.justification_bits.dtype == jnp.bool_
            else bitvector4_chunk(just.justification_bits.astype(bool))
        ),
        slot_of["previous_justified_checkpoint"]: checkpoint_root(
            just.prev_justified_epoch, just.prev_justified_root
        ),
        slot_of["current_justified_checkpoint"]: checkpoint_root(
            just.cur_justified_epoch, just.cur_justified_root
        ),
        slot_of["finalized_checkpoint"]: checkpoint_root(
            just.finalized_epoch, just.finalized_root
        ),
    }
    return dyn


# --------------------------------------------- incremental (forest) path --
#
# The full path above re-hashes every tree each epoch. The incremental
# path keeps the three big subtrees resident as merkle_inc forests (ALL
# internal levels in HBM, donated buffers) and re-hashes only the
# O(dirty x depth) ancestor paths the accounting epoch actually
# dirtied: effective balances move only on hysteresis crossings, the
# balance/score columns diff chunk-wise, and the participation list is
# STATIC inside the resident loop (its list root is computed once at
# forest build and reused — the full path re-treed it every epoch for
# the same value). Roots are bit-identical to the full recompute by
# construction: same tree shapes, same pads, same folds, the shared
# _small_dynamic_roots, the shared combine.


class StateForest(NamedTuple):
    """Device-resident incremental tree state (a pure-array pytree; the
    resident runner donates every leaf so epoch N+1 updates epoch N's
    buffers in place)."""

    val_nodes: jnp.ndarray  # u32[S, 2^(dvl+1)-1, 8] validator-root forest
    bal_nodes: jnp.ndarray  # u32[S, 2^(dbl+1)-1, 8] balance-chunk forest
    inact_nodes: jnp.ndarray | None  # scores forest (None pre-altair)
    part_root: jnp.ndarray  # u32[8] previous-participation LIST root (static)


class ForestPlan(NamedTuple):
    """Hashable static plan of an incremental forest — part of the
    resident compile key. Capacities/thresholds are PER SHARD."""

    depth_val: int  # validator-leaf tree depth (global)
    depth_bal: int  # u64-chunk tree depth (global; scores share it)
    shards: int  # pow2 leaf-axis shard count (1 = single device)
    cap_val: int  # dirty-capacity compile bucket, validator leaves
    cap_bal: int  # dirty-capacity compile bucket, chunk leaves
    dense_val: int  # dirty count past which the dense rebuild wins
    dense_bal: int
    has_inact: bool  # spec has inactivity_scores (altair+)


def forest_plan(meta: StateRootMeta, mesh=None, dirty_cap: int | None = None) -> ForestPlan:
    """Plan an incremental forest for this registry shape: tree depths
    from the leaf counts, shard count from the mesh (pow2-dividing or
    1), dirty capacities from the serve bucket grid
    (serve/buckets.inc_dirty_buckets — env-snapshotted HERE, never
    inside a trace), dense-fallback thresholds from the measured
    crossover model (buckets.inc_dense_count). `dirty_cap` overrides
    the default per-epoch dirty-leaf hint (n/256)."""
    from eth_consensus_specs_tpu.ops import merkle_inc
    from eth_consensus_specs_tpu.serve import buckets

    n = meta.n_validators
    depth_val = max(n - 1, 0).bit_length()
    chunks = (n + 3) // 4
    depth_bal = max(chunks - 1, 0).bit_length()
    shards = merkle_inc.forest_shards(min(depth_val, depth_bal), mesh)
    slog2 = (shards - 1).bit_length()
    hint = int(dirty_cap) if dirty_cap else max(n >> 8, 8)
    cap_val = min(buckets.inc_dirty_bucket(-(-hint // shards)), (1 << depth_val) // shards)
    cap_bal = min(
        buckets.inc_dirty_bucket(-(-max(hint // 4, 1) // shards)),
        (1 << depth_bal) // shards,
    )
    names = {name for _, name in meta.dynamic_slots}
    return ForestPlan(
        depth_val=depth_val,
        depth_bal=depth_bal,
        shards=shards,
        cap_val=cap_val,
        cap_bal=cap_bal,
        dense_val=buckets.inc_dense_count(depth_val - slog2, cap_val, leaf_hashes=3),
        dense_bal=buckets.inc_dense_count(depth_bal - slog2, cap_bal),
        has_inact="inactivity_scores" in names,
    )


def _u64_chunk_leaves(vals: jnp.ndarray, n: int, depth: int) -> jnp.ndarray:
    """u64[n] column -> u32[2^depth, 8] packed SSZ chunk leaf level
    (zero pads past the live chunks — the same virtual padding the full
    path's _pad_pow2 applies)."""
    if n % 4:
        vals = jnp.concatenate([vals, jnp.zeros(4 - n % 4, jnp.uint64)])
    leaves = packed_u64_leaves(vals, vals.shape[0])
    return _pad_pow2(leaves, depth)


def _pad_col(vals: jnp.ndarray, cap: int) -> jnp.ndarray:
    pad = cap - vals.shape[0]
    if pad:
        vals = jnp.concatenate([vals, jnp.zeros((pad, *vals.shape[1:]), vals.dtype)])
    return vals


def _validator_leaf_inputs(
    arrays: StateRootArrays, n: int, effective_balance: jnp.ndarray, plan: ForestPlan
) -> tuple:
    """The sharded per-leaf sources of the validator-root leaves: the
    new effective balances plus the static nodes, padded to the leaf
    level and reshaped [S, Ll, ...]."""
    lv = 1 << plan.depth_val
    s = plan.shards
    live = jnp.arange(lv, dtype=jnp.int32) < jnp.int32(n)
    return (
        _pad_col(effective_balance, lv).reshape(s, lv // s),
        _pad_col(arrays.slashed_chunk, lv).reshape(s, lv // s, 8),
        _pad_col(arrays.val_node_a, lv).reshape(s, lv // s, 8),
        _pad_col(arrays.val_node_f, lv).reshape(s, lv // s, 8),
        live.reshape(s, lv // s),
    )


def _validator_leaf_fn(inputs: tuple, idx: jnp.ndarray) -> jnp.ndarray:
    """Validator-root leaves at the given (shard-local) indices — the
    SHARED _validator_leaf_rows chain on the gathered rows; pad indices
    past the registry produce the SSZ zero chunk, matching the full
    path's _pad_pow2."""
    eff_l, slashed_l, a_l, f_l, live_l = inputs
    leaf = _validator_leaf_rows(eff_l[idx], slashed_l[idx], a_l[idx], f_l[idx])
    return jnp.where(live_l[idx][:, None], leaf, jnp.zeros_like(leaf))


def build_state_forest(
    arrays: StateRootArrays,
    meta: StateRootMeta,
    plan: ForestPlan,
    balances: jnp.ndarray,
    effective_balance: jnp.ndarray,
    inactivity_scores: jnp.ndarray,
) -> StateForest:
    """One-time forest ingest (traceable; jit it once per shape): every
    validator root + all internal levels of the three big trees, plus
    the static previous-participation list root."""
    from eth_consensus_specs_tpu.ops import merkle_inc

    n = meta.n_validators
    s = plan.shards
    lv = 1 << plan.depth_val
    inputs = _validator_leaf_inputs(arrays, n, effective_balance, plan)
    flat = tuple(a.reshape(-1, *a.shape[2:]) for a in inputs)
    val_leaves = _validator_leaf_fn(flat, jnp.arange(lv, dtype=jnp.int32))
    val_nodes = merkle_inc.build_forest(val_leaves, s)
    bal_nodes = merkle_inc.build_forest(
        _u64_chunk_leaves(balances, n, plan.depth_bal), s
    )
    inact_nodes = None
    if plan.has_inact:
        inact_nodes = merkle_inc.build_forest(
            _u64_chunk_leaves(inactivity_scores, n, plan.depth_bal), s
        )
    part_tail = u8_list_tail(arrays.prev_part_flags, n, PARTICIPATION_LIMIT_CHUNKS_LOG2)
    part_root = list_roots({0: part_tail}, arrays.zerohashes)[0]
    return StateForest(
        val_nodes=val_nodes,
        bal_nodes=bal_nodes,
        inact_nodes=inact_nodes,
        part_root=part_root,
    )


def _forest_tails(plan: ForestPlan, n: int, slot_of: dict, subtree_roots: dict) -> dict:
    """The forest trees' roots as list tails by top-level slot, at the
    plan's depths (the participation list is static in the resident
    loop: it has no tail)."""
    shape = {
        "validators": (plan.depth_val, VALIDATOR_REGISTRY_LIMIT_LOG2),
        "balances": (plan.depth_bal, BALANCE_LIMIT_CHUNKS_LOG2),
        "inactivity_scores": (plan.depth_bal, BALANCE_LIMIT_CHUNKS_LOG2),
    }
    return {
        slot_of[name]: ListTail(root, *shape[name], n)
        for name, root in subtree_roots.items()
    }


def state_root_inc_real_hashes(meta: StateRootMeta, plan: ForestPlan) -> int:
    """Compressions one INCREMENTAL post-epoch root executes under the
    capacity model — the honest dirty-path node count for roofline /
    work-bytes accounting. Per tree the kernel runs either the sparse
    path (exactly cap x (depth + leaf hashes) compressions, padding
    duplicates included) or the dense rebuild; the static model takes
    the MINIMUM of the two, so implied traffic is never overstated (a
    dense epoch does more work than claimed, never less roofline-legal
    work). Folds, length mixes, checkpoints, and the top combine are
    counted exactly like state_root_real_hashes."""
    from eth_consensus_specs_tpu.ops import merkle_inc

    n = meta.n_validators
    s = plan.shards
    slog2 = (s - 1).bit_length()

    def tree_cost(depth: int, cap: int, leaf_hashes: int, dense_leaf_total: int) -> int:
        sparse = s * merkle_inc.inc_update_hashes(depth - slog2, cap, leaf_hashes)
        dense = merkle_inc.build_levels_hashes(depth - slog2) * s + dense_leaf_total
        return min(sparse, dense) + max(s - 1, 0)  # + the top combine

    hashes = tree_cost(plan.depth_val, plan.cap_val, 3, 3 * n)
    hashes += tree_cost(plan.depth_bal, plan.cap_bal, 0, 0)
    folds = (VALIDATOR_REGISTRY_LIMIT_LOG2 - plan.depth_val) + (
        BALANCE_LIMIT_CHUNKS_LOG2 - plan.depth_bal
    )
    mixes = 2
    if plan.has_inact:
        hashes += tree_cost(plan.depth_bal, plan.cap_bal, 0, 0)
        folds += BALANCE_LIMIT_CHUNKS_LOG2 - plan.depth_bal
        mixes += 1
    return hashes + folds + mixes + 3 + (1 << meta.top_depth)


def post_epoch_state_root_inc(
    arrays: StateRootArrays,
    meta: StateRootMeta,
    plan: ForestPlan,
    forest: StateForest,
    old_balances: jnp.ndarray,
    old_effective_balance: jnp.ndarray,
    old_inactivity_scores: jnp.ndarray,
    balances: jnp.ndarray,
    effective_balance: jnp.ndarray,
    inactivity_scores: jnp.ndarray,
    just,
    mesh=None,
) -> tuple[StateForest, jnp.ndarray]:
    """The incremental full post-epoch state root (traceable; composes
    under the resident epoch jit). Diffs old vs new columns into
    per-tree dirty masks, applies them through the forest kernels
    (sparse path rehash or dense rebuild, per shard), and combines the
    same top-level container the full path does. Returns (forest, root)
    with root bit-identical to post_epoch_state_root on the same
    columns."""
    from eth_consensus_specs_tpu.ops import merkle_inc

    n = meta.n_validators
    s = plan.shards
    zh = arrays.zerohashes
    slot_of = {name: i for i, name in meta.dynamic_slots}
    dyn: dict[int, jnp.ndarray] = {}

    # -- validator registry: dirty = hysteresis crossings --------------
    lv = 1 << plan.depth_val
    mask_val = _pad_col(old_effective_balance != effective_balance, lv)
    inputs = _validator_leaf_inputs(arrays, n, effective_balance, plan)
    val_nodes, sub_val = merkle_inc.forest_apply(
        forest.val_nodes,
        mask_val.reshape(s, lv // s),
        inputs,
        _validator_leaf_fn,
        plan.cap_val,
        plan.dense_val,
        mesh=mesh if s > 1 else None,
    )
    subs = {"validators": sub_val}

    # -- u64 list columns: chunk-wise diff ------------------------------
    def u64_tree(nodes, old_vals, new_vals):
        old_leaves = _u64_chunk_leaves(old_vals, n, plan.depth_bal)
        new_leaves = _u64_chunk_leaves(new_vals, n, plan.depth_bal)
        mask = jnp.any(old_leaves != new_leaves, axis=-1)
        lb = 1 << plan.depth_bal
        nodes, sub = merkle_inc.forest_apply(
            nodes,
            mask.reshape(s, lb // s),
            (new_leaves.reshape(s, lb // s, 8),),
            lambda inputs, idx: inputs[0][idx],
            plan.cap_bal,
            plan.dense_bal,
            mesh=mesh if s > 1 else None,
        )
        return nodes, sub

    bal_nodes, subs["balances"] = u64_tree(forest.bal_nodes, old_balances, balances)
    inact_nodes = forest.inact_nodes
    if plan.has_inact and "inactivity_scores" in slot_of:
        inact_nodes, subs["inactivity_scores"] = u64_tree(
            forest.inact_nodes, old_inactivity_scores, inactivity_scores
        )
    dyn.update(list_roots(_forest_tails(plan, n, slot_of, subs), zh))

    # -- static-in-the-loop participation lists -------------------------
    if "previous_epoch_participation" in slot_of:
        dyn[slot_of["previous_epoch_participation"]] = forest.part_root
        dyn[slot_of["current_epoch_participation"]] = jnp.asarray(
            _zero_u8_list_root_words(n)
        )

    dyn.update(_small_dynamic_roots(slot_of, just))
    forest = StateForest(
        val_nodes=val_nodes,
        bal_nodes=bal_nodes,
        inact_nodes=inact_nodes,
        part_root=forest.part_root,
    )
    return forest, combine_state_root(arrays, meta, dyn)


def state_root_from_forest(
    arrays: StateRootArrays,
    meta: StateRootMeta,
    plan: ForestPlan,
    forest: StateForest,
    just,
) -> jnp.ndarray:
    """The full post-epoch state root recomputed from a RESIDENT forest
    with ZERO dirty work (traceable) — the digest gate checkpoint
    manifests and restore verification share with the incremental epoch
    path. Same folds, same length mixes, same _small_dynamic_roots,
    same top combine as post_epoch_state_root_inc, so a root computed
    here bit-matches the one the resident chain would have produced on
    the same forest — which is exactly what lets a restore REFUSE to
    serve a forest whose recomputed root disagrees with its manifest."""
    from eth_consensus_specs_tpu.ops import merkle_inc

    n = meta.n_validators
    zh = arrays.zerohashes
    slot_of = {name: i for i, name in meta.dynamic_slots}
    dyn: dict[int, jnp.ndarray] = {}

    subs = {
        "validators": merkle_inc.forest_root(forest.val_nodes),
        "balances": merkle_inc.forest_root(forest.bal_nodes),
    }
    if plan.has_inact and "inactivity_scores" in slot_of:
        subs["inactivity_scores"] = merkle_inc.forest_root(forest.inact_nodes)
    dyn.update(list_roots(_forest_tails(plan, n, slot_of, subs), zh))
    if "previous_epoch_participation" in slot_of:
        dyn[slot_of["previous_epoch_participation"]] = forest.part_root
        dyn[slot_of["current_epoch_participation"]] = jnp.asarray(
            _zero_u8_list_root_words(n)
        )
    dyn.update(_small_dynamic_roots(slot_of, just))
    return combine_state_root(arrays, meta, dyn)
