"""Host oracle for the device state-root pipeline (ops/state_root.py).

Same tree, different engine: numpy word-wrangling + the native C SHA-256
core (SHA-NI when the host has it), no XLA anywhere in the hash path.
It is the independent leg of the tests, of `chip_smoke.py` and of the
service's host fallback: device result == host-oracle result on the
SAME inputs.

The reference's equivalent of this oracle is its per-node hashlib path
(reference: tests/core/pyspec/eth2spec/utils/merkle_minimal.py:47-91 and
hash_function.py:8-9); the functions here mirror ops/state_root.py
one-for-one so a disagreement localizes the divergent subtree.
"""

from __future__ import annotations

import numpy as np

from eth_consensus_specs_tpu.ops.state_root import (
    BALANCE_LIMIT_CHUNKS_LOG2,
    PARTICIPATION_LIMIT_CHUNKS_LOG2,
    VALIDATOR_REGISTRY_LIMIT_LOG2,
    StateRootMeta,
    _zero_u8_list_root_words,
    zerohash_words,
)


def _hash_pairs_np(msgs_words: np.ndarray) -> np.ndarray:
    """u32[N, 16] word rows (one 64-byte message per row, BE words) ->
    u32[N, 8] digest word rows, through the native sha core with a
    hashlib fallback."""
    import hashlib

    from eth_consensus_specs_tpu import native

    data = np.ascontiguousarray(msgs_words.astype(">u4")).tobytes()
    if native.available():
        out = native.sha256_pairs(data)
    else:
        out = b"".join(
            hashlib.sha256(data[i : i + 64]).digest() for i in range(0, len(data), 64)
        )
    return np.frombuffer(out, dtype=">u4").astype(np.uint32).reshape(-1, 8)


def hash_rows_np(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """H(a || b) rowwise for u32[N, 8] chunks."""
    return _hash_pairs_np(np.concatenate([a, b], axis=-1).reshape(-1, 16))


def tree_root_np(leaves: np.ndarray, depth: int) -> np.ndarray:
    """Exact logical Merkle reduction of u32[2**depth, 8] -> u32[8]."""
    assert leaves.shape[0] == 1 << depth
    buf = leaves
    for _ in range(depth):
        buf = hash_rows_np(buf[0::2], buf[1::2])
    return buf[0]


def tree_root_chain_np(
    base: np.ndarray, depth: int, chain: int, salt: np.ndarray
) -> np.ndarray:
    """Host recompute of a chained device tree: `chain` iterations of
    root = tree(base ^ root), starting from the salt words.  Only the LOGICAL nodes are hashed — the device
    kernel's full-width overhead never reaches the root value."""
    acc = salt.astype(np.uint32)
    for _ in range(chain):
        acc = tree_root_np(base ^ acc[None, :], depth)
    return acc


def pad_pow2_np(leaves: np.ndarray, depth: int) -> np.ndarray:
    pad = (1 << depth) - leaves.shape[0]
    if pad:
        leaves = np.concatenate([leaves, np.zeros((pad, 8), np.uint32)], axis=0)
    return leaves


def u64_chunk_words_np(val: int) -> np.ndarray:
    b = int(val).to_bytes(8, "little") + b"\x00" * 24
    return np.frombuffer(b, dtype=">u4").astype(np.uint32)


def packed_u64_leaves_np(vals: np.ndarray) -> np.ndarray:
    """u64[n] -> u32[ceil(n/4), 8] SSZ packed chunk words (BE)."""
    n = vals.shape[0]
    if n % 4:
        vals = np.concatenate([vals, np.zeros(4 - n % 4, np.uint64)])
    raw = vals.astype("<u8").tobytes()
    return np.frombuffer(raw, dtype=">u4").astype(np.uint32).reshape(-1, 8)


def packed_u8_leaves_np(vals: np.ndarray) -> np.ndarray:
    n = vals.shape[0]
    if n % 32:
        vals = np.concatenate([vals, np.zeros(32 - n % 32, np.uint8)])
    raw = vals.astype(np.uint8).tobytes()
    return np.frombuffer(raw, dtype=">u4").astype(np.uint32).reshape(-1, 8)


def fold_to_limit_np(
    root: np.ndarray, depth: int, limit_log2: int, zh: np.ndarray
) -> np.ndarray:
    for d in range(depth, limit_log2):
        root = hash_rows_np(root[None, :], zh[d][None, :])[0]
    return root


def mix_length_np(root: np.ndarray, length: int) -> np.ndarray:
    return hash_rows_np(root[None, :], u64_chunk_words_np(length)[None, :])[0]


def u64_list_root_np(vals: np.ndarray, n: int, limit_log2: int, zh) -> np.ndarray:
    leaves = packed_u64_leaves_np(vals)
    chunks = (n + 3) // 4
    depth = max(chunks - 1, 0).bit_length() if n else 0
    sub = tree_root_np(pad_pow2_np(leaves, depth), depth)
    return mix_length_np(fold_to_limit_np(sub, depth, limit_log2, zh), n)


def u8_list_root_np(vals: np.ndarray, n: int, limit_log2: int, zh) -> np.ndarray:
    leaves = packed_u8_leaves_np(vals)
    chunks = (n + 31) // 32
    depth = max(chunks - 1, 0).bit_length() if n else 0
    sub = tree_root_np(pad_pow2_np(leaves, depth), depth)
    return mix_length_np(fold_to_limit_np(sub, depth, limit_log2, zh), n)


def checkpoint_root_np(epoch: int, root_bytes: np.ndarray) -> np.ndarray:
    r_words = np.frombuffer(
        np.ascontiguousarray(root_bytes, np.uint8).tobytes(), dtype=">u4"
    ).astype(np.uint32)
    return hash_rows_np(u64_chunk_words_np(epoch)[None, :], r_words[None, :])[0]


def bitvector4_chunk_np(bits: np.ndarray) -> np.ndarray:
    byte = int(bits[0]) | (int(bits[1]) << 1) | (int(bits[2]) << 2) | (int(bits[3]) << 3)
    chunk = np.zeros(8, np.uint32)
    chunk[0] = np.uint32(byte << 24)
    return chunk


def validator_registry_root_np(
    val_node_a: np.ndarray,
    val_node_f: np.ndarray,
    slashed_chunk: np.ndarray,
    effective_balance: np.ndarray,
    zh: np.ndarray,
) -> np.ndarray:
    n = effective_balance.shape[0]
    node_b = hash_rows_np(_eb_chunks_fast(effective_balance), slashed_chunk)
    node_e = hash_rows_np(val_node_a, node_b)
    roots = hash_rows_np(node_e, val_node_f)
    depth = max(n - 1, 0).bit_length()
    sub = tree_root_np(pad_pow2_np(roots, depth), depth)
    full = fold_to_limit_np(sub, depth, VALIDATOR_REGISTRY_LIMIT_LOG2, zh)
    return mix_length_np(full, n)


def _eb_chunks_fast(vals: np.ndarray) -> np.ndarray:
    """u64[n] -> per-VALIDATOR chunk words (one u64 in a 32-byte chunk)."""
    n = vals.shape[0]
    out = np.zeros((n, 32), np.uint8)
    out[:, :8] = np.frombuffer(vals.astype("<u8").tobytes(), np.uint8).reshape(n, 8)
    return np.frombuffer(out.tobytes(), dtype=">u4").astype(np.uint32).reshape(n, 8)


def post_epoch_state_root_np(
    arrays_np, meta: StateRootMeta, balances, effective_balance, inactivity_scores, just_np
) -> np.ndarray:
    """Host mirror of ops/state_root.post_epoch_state_root.  `arrays_np`
    is the StateRootArrays pytree as numpy; `just_np` a JustificationState
    as numpy."""
    n = meta.n_validators
    zh = zerohash_words(41)
    slot_of = {name: i for i, name in meta.dynamic_slots}
    dyn: dict[int, np.ndarray] = {}
    dyn[slot_of["validators"]] = validator_registry_root_np(
        np.asarray(arrays_np.val_node_a),
        np.asarray(arrays_np.val_node_f),
        np.asarray(arrays_np.slashed_chunk),
        np.asarray(effective_balance),
        zh,
    )
    dyn[slot_of["balances"]] = u64_list_root_np(
        np.asarray(balances), n, BALANCE_LIMIT_CHUNKS_LOG2, zh
    )
    if "inactivity_scores" in slot_of:
        dyn[slot_of["inactivity_scores"]] = u64_list_root_np(
            np.asarray(inactivity_scores), n, BALANCE_LIMIT_CHUNKS_LOG2, zh
        )
    if "previous_epoch_participation" in slot_of:
        dyn[slot_of["previous_epoch_participation"]] = u8_list_root_np(
            np.asarray(arrays_np.prev_part_flags), n, PARTICIPATION_LIMIT_CHUNKS_LOG2, zh
        )
        dyn[slot_of["current_epoch_participation"]] = _zero_u8_list_root_words(n).astype(
            np.uint32
        )
    dyn[slot_of["justification_bits"]] = bitvector4_chunk_np(
        np.asarray(just_np.justification_bits).astype(bool)
    )
    dyn[slot_of["previous_justified_checkpoint"]] = checkpoint_root_np(
        int(just_np.prev_justified_epoch), np.asarray(just_np.prev_justified_root)
    )
    dyn[slot_of["current_justified_checkpoint"]] = checkpoint_root_np(
        int(just_np.cur_justified_epoch), np.asarray(just_np.cur_justified_root)
    )
    dyn[slot_of["finalized_checkpoint"]] = checkpoint_root_np(
        int(just_np.finalized_epoch), np.asarray(just_np.finalized_root)
    )
    chunks = np.array(np.asarray(arrays_np.top_chunks), np.uint32, copy=True)
    for slot, root in dyn.items():
        chunks[slot] = root
    return tree_root_np(chunks, meta.top_depth)


def resident_root_acc_host(spec, cols, just, n_epochs: int, static) -> np.ndarray:
    """Host recompute of parallel/resident.run_epochs(..., with_root="state")
    .root_acc: the accounting advance runs through the SAME kernel jitted
    on the current (CPU-pinned) backend one epoch at a time, while every
    per-epoch state root goes through this module's native-sha tree — an
    execution path with no shared XLA graph and no shared hash engine with
    the device run being checked."""
    import jax
    import jax.numpy as jnp

    from eth_consensus_specs_tpu.ops.altair_epoch import (
        AltairEpochParams,
        altair_epoch_accounting_impl,
    )

    params = AltairEpochParams.from_spec(spec)
    arrays, meta = static
    arrays_np = jax.tree_util.tree_map(np.asarray, arrays)

    @jax.jit
    def advance(cols, just):
        res = altair_epoch_accounting_impl(params, cols, just)
        cols = cols._replace(
            balance=res.balance,
            effective_balance=res.effective_balance,
            inactivity_scores=res.inactivity_scores,
        )
        just = just._replace(
            current_epoch=just.current_epoch + jnp.uint64(1),
            justification_bits=res.justification_bits,
            prev_justified_epoch=res.prev_justified_epoch,
            prev_justified_root=res.prev_justified_root,
            cur_justified_epoch=res.cur_justified_epoch,
            cur_justified_root=res.cur_justified_root,
            finalized_epoch=res.finalized_epoch,
            finalized_root=res.finalized_root,
        )
        return cols, just

    acc = np.zeros(8, np.uint32)
    for _ in range(n_epochs):
        cols, just = advance(cols, just)
        just_np = jax.tree_util.tree_map(np.asarray, just)
        acc = acc ^ post_epoch_state_root_np(
            arrays_np,
            meta,
            np.asarray(cols.balance),
            np.asarray(cols.effective_balance),
            np.asarray(cols.inactivity_scores),
            just_np,
        )
    return acc
