"""Plain reference for hash_tree_root(BeaconState) after the accounting
epoch, from the columns the service is handed: hashlib and numpy, nothing of
the program. The layout is Altair's BeaconState of the consensus-specs
(`specs/altair/beacon-chain.md`), mainnet preset:

* a validator's root is H(H(A, B), F) with A = H(pubkey_root,
  withdrawal_credentials) and F = H(H(aee, ae), H(exit, withdrawable)) given
  as static nodes, and B = H(chunk(effective_balance), chunk(slashed));
* validators, balances, inactivity_scores and previous_epoch_participation
  are lists: the tree over the padded chunks, folded with zero hashes to the
  depth of the list's limit, then mixed with the length;
* current_epoch_participation is all zero after the rotation;
* justification_bits and the three checkpoints are small;
* every other field's root is a static chunk.
"""

from __future__ import annotations

import hashlib

import numpy as np

_sha = hashlib.sha256

# BeaconState (Altair) in field order; the top tree has 32 leaves
FIELDS = (
    "genesis_time", "genesis_validators_root", "slot", "fork", "latest_block_header",
    "block_roots", "state_roots", "historical_roots", "eth1_data", "eth1_data_votes",
    "eth1_deposit_index", "validators", "balances", "randao_mixes", "slashings",
    "previous_epoch_participation", "current_epoch_participation", "justification_bits",
    "previous_justified_checkpoint", "current_justified_checkpoint",
    "finalized_checkpoint", "inactivity_scores", "current_sync_committee",
    "next_sync_committee",
)
TOP_DEPTH = 5
VALIDATOR_LIMIT_LOG2 = 40  # List[Validator, 2**40]
U64_LIMIT_CHUNKS_LOG2 = 38  # List[uint64, 2**40]: four to a chunk
U8_LIMIT_CHUNKS_LOG2 = 35  # List[uint8, 2**40]: thirty-two to a chunk


def zero_hashes(depth: int) -> list[bytes]:
    out = [bytes(32)]
    for _ in range(depth):
        out.append(_sha(out[-1] + out[-1]).digest())
    return out


ZERO = zero_hashes(41)


def hash_pairs(data: bytes) -> bytes:
    """The hashes of consecutive 64-byte blocks, joined."""
    return b"".join(_sha(data[i : i + 64]).digest() for i in range(0, len(data), 64))


def hash_rows(left: np.ndarray, right: np.ndarray) -> np.ndarray:
    """H(left[i] || right[i]) for uint8[n, 32] rows."""
    out = hash_pairs(np.concatenate([left, right], axis=1).tobytes())
    return np.frombuffer(out, np.uint8).reshape(-1, 32)


def words_to_bytes(words: np.ndarray) -> np.ndarray:
    """u32[..., 8] big-endian digest words -> uint8[..., 32]."""
    return np.ascontiguousarray(words).astype(">u4").view(np.uint8).reshape(*words.shape[:-1], 32)


def subtree_root(chunks: bytes, depth: int) -> bytes:
    """Root of the 2**depth-leaf tree over the 32-byte chunks, zero padded."""
    count = len(chunks) // 32
    level = 0
    while level < depth:
        if count % 2:
            chunks += ZERO[level]
            count += 1
        chunks = hash_pairs(chunks)
        count //= 2
        level += 1
    return chunks if count else ZERO[depth]


def list_root(chunks: bytes, limit_log2: int, length: int) -> bytes:
    count = len(chunks) // 32
    depth = max(count - 1, 0).bit_length()
    root = subtree_root(chunks, depth)
    for d in range(depth, limit_log2):
        root = _sha(root + ZERO[d]).digest()
    return _sha(root + length.to_bytes(8, "little") + bytes(24)).digest()


def _u64_chunks(values: np.ndarray) -> np.ndarray:
    out = np.zeros((values.shape[0], 32), np.uint8)
    out[:, :8] = values.astype("<u8").view(np.uint8).reshape(-1, 8)
    return out


def _packed(values: np.ndarray, dtype: str) -> bytes:
    raw = values.astype(dtype).tobytes()
    return raw + bytes(-len(raw) % 32)


def _checkpoint(epoch, root) -> bytes:
    return _sha(int(epoch).to_bytes(8, "little") + bytes(24) + bytes(np.asarray(root, np.uint8))).digest()


def registry_root(static: dict, effective_balance: np.ndarray) -> bytes:
    n = effective_balance.shape[0]
    node_b = hash_rows(_u64_chunks(effective_balance), words_to_bytes(static["slashed_chunk"]))
    node_e = hash_rows(words_to_bytes(static["val_node_a"]), node_b)
    leaves = hash_rows(node_e, words_to_bytes(static["val_node_f"]))
    return list_root(leaves.tobytes(), VALIDATOR_LIMIT_LOG2, n)


def state_root(static: dict, balances, effective_balance, inactivity_scores, just: dict,
               registry: bytes | None = None) -> bytes:
    """The 32-byte root. `static` holds the arrays the service was given
    (val_node_a, val_node_f, slashed_chunk as u32 big-endian words,
    prev_part_flags u8[n], top_chunks u32[32, 8]); `just` the small
    justification fields. `registry` replaces the validators' list root: the
    control passes a stale one."""
    n = int(np.asarray(balances).shape[0])
    top = [bytes(row) for row in words_to_bytes(np.asarray(static["top_chunks"]))]
    at = FIELDS.index
    top[at("validators")] = registry or registry_root(static, np.asarray(effective_balance))
    top[at("balances")] = list_root(_packed(np.asarray(balances), "<u8"), U64_LIMIT_CHUNKS_LOG2, n)
    top[at("inactivity_scores")] = list_root(
        _packed(np.asarray(inactivity_scores), "<u8"), U64_LIMIT_CHUNKS_LOG2, n
    )
    top[at("previous_epoch_participation")] = list_root(
        _packed(np.asarray(static["prev_part_flags"]), "u1"), U8_LIMIT_CHUNKS_LOG2, n
    )
    top[at("current_epoch_participation")] = list_root(
        _packed(np.zeros(n, np.uint8), "u1"), U8_LIMIT_CHUNKS_LOG2, n
    )
    bits = np.asarray(just["justification_bits"]).astype(bool)
    top[at("justification_bits")] = bytes([sum(int(b) << i for i, b in enumerate(bits))]) + bytes(31)
    top[at("previous_justified_checkpoint")] = _checkpoint(
        just["prev_justified_epoch"], just["prev_justified_root"]
    )
    top[at("current_justified_checkpoint")] = _checkpoint(
        just["cur_justified_epoch"], just["cur_justified_root"]
    )
    top[at("finalized_checkpoint")] = _checkpoint(just["finalized_epoch"], just["finalized_root"])
    return subtree_root(b"".join(top), TOP_DEPTH)
