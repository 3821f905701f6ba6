"""Always-on device/host divergence watchdog.

An accelerator platform acknowledging work before executing it is only
caught by *continuously* coupling device results to host recomputes.
This module samples the kernel hot paths at an env-tunable rate and recomputes a
(salted, where an extra dispatch is involved) slice of each device
result on the host with an engine that shares nothing with XLA
(hashlib / the pure spec loop / the host pairing). Match/mismatch lands
in first-class metrics:

    watchdog.checks / watchdog.divergences            (global)
    watchdog.<kernel>.checks / .divergences           (per kernel)

plus a structured event per divergence with enough context to reproduce.

Tuning: ``ETH_SPECS_OBS_WATCHDOG`` is the sampling rate in [0, 1] —
``0`` disables, ``1`` checks every call (CI smoke), default ``0.05``
(every ~20th call per kernel; the FIRST call is always checked so every
process gets at least one verdict per touched kernel).
"""

from __future__ import annotations

import hashlib
import os
import threading

import numpy as np

from . import flight, gates
from .registry import get_registry, obs_enabled

_DEFAULT_RATE = 0.05

_lock = threading.Lock()
_calls: dict[str, int] = {}


def _reinit_lock_after_fork_in_child() -> None:
    # fork-safety: a serving thread can be inside should_check when the
    # gen pool forks; the child's first sampled kernel call must not
    # block on a lock held by a thread that does not exist there
    global _lock
    _lock = threading.Lock()


os.register_at_fork(after_in_child=_reinit_lock_after_fork_in_child)


def sampling_rate() -> float:
    raw = os.environ.get("ETH_SPECS_OBS_WATCHDOG", "")
    if not raw:
        return _DEFAULT_RATE
    try:
        return min(max(float(raw), 0.0), 1.0)
    except ValueError:
        return _DEFAULT_RATE


def should_check(kernel: str) -> bool:
    """Deterministic interval sampling per kernel: call k is checked when
    k % round(1/rate) == 1, so the first call always is — a short test
    process still produces a verdict for every kernel it touched."""
    if not obs_enabled():
        return False
    rate = sampling_rate()
    if rate <= 0.0:
        return False
    with _lock:
        _calls[kernel] = n = _calls.get(kernel, 0) + 1
    interval = max(1, round(1.0 / rate))
    return n % interval == 1 or interval == 1


def call_salt(kernel: str) -> int:
    """Deterministic per-call salt (Weyl sequence over the call counter):
    varies every sampled call, so a platform-side (program, input) result
    cache can never replay a previous probe's answer."""
    with _lock:
        n = _calls.get(kernel, 0)
    return (n * 0x9E3779B1 + 0x7F4A7C15) & 0xFFFFFFFF


def record(kernel: str, ok: bool, detail: dict | None = None) -> None:
    reg = get_registry()
    reg.count("watchdog.checks")
    reg.count(f"watchdog.{kernel}.checks")
    if not ok:
        reg.count("watchdog.divergences")
        reg.count(f"watchdog.{kernel}.divergences")
        event = {"kind": "watchdog.divergence", "kernel": kernel}
        if detail:
            event.update(detail)
        reg.emit(event)
        # a divergence is THE postmortem moment: dump the flight ring +
        # registry so the black box holds what led up to the wrong answer
        flight.trigger_dump("watchdog.divergence", detail=kernel, extra={"event": event})


# ------------------------------------------------------------ kernel checks --


def _be_words_to_bytes(row: np.ndarray) -> bytes:
    return row.astype(">u4", order="C").view(np.uint8).tobytes()


def _sample_rows(m: int, k: int = 3) -> list[int]:
    return sorted({0, m // 2, m - 1} if m >= k else set(range(m)))


def check_sha256_slice(words, digests, kernel: str = "sha256") -> bool:
    """Sampled rows of the batched 64-byte hash: device digest vs hashlib
    on the SAME input words. No extra device work — the output is already
    in hand at the call site; only the sampled rows (96 B each) cross to
    the host."""
    ok = True
    rows = _sample_rows(int(words.shape[0]))
    for i in rows:
        msg = _be_words_to_bytes(np.asarray(words[i]))
        expect = hashlib.sha256(msg).digest()
        got = _be_words_to_bytes(np.asarray(digests[i]))
        if got != expect:
            ok = False
            record(
                kernel,
                False,
                {"row": i, "expected": expect.hex()[:32], "got": got.hex()[:32]},
            )
            break
    if ok:
        record(kernel, True)
    return ok


def host_tree_root_words(words: np.ndarray) -> bytes:
    """Pairwise hashlib reduction of uint32[2**d, 8] big-endian leaf words
    to the 32-byte root — the zero-XLA host oracle for tree slices."""
    level = [
        _be_words_to_bytes(words[i]) for i in range(words.shape[0])
    ]
    while len(level) > 1:
        level = [
            hashlib.sha256(level[i] + level[i + 1]).digest()
            for i in range(0, len(level), 2)
        ]
    return level[0]


_SLICE_DEPTH = 6  # 64-leaf salted probe for trees too big to replay fully
_FULL_REPLAY_MAX_DEPTH = 12  # <= 4095 hashlib hashes: cheap to replay whole


def check_merkle_root(words: np.ndarray, depth: int, root: bytes) -> bool:
    """Device tree root vs host. Small trees are replayed whole through
    hashlib. Large trees get a salted-slice probe: 2**6 sampled leaves
    XOR a per-call salt run through the SAME device kernel and recomputed
    on host — an extra (tiny) dispatch whose answer the platform cannot
    have cached, checking the hash engine is actually executing."""
    if depth <= _FULL_REPLAY_MAX_DEPTH:
        ok = host_tree_root_words(words) == root
        record("merkle", ok, None if ok else {"depth": depth, "mode": "full-replay"})
        return ok
    from jax import numpy as jnp

    from eth_consensus_specs_tpu.ops.merkle import _tree_root_fused

    salt = np.uint32(call_salt("merkle"))
    step = max(words.shape[0] // (1 << _SLICE_DEPTH), 1)
    sampled = np.ascontiguousarray(words[::step][: 1 << _SLICE_DEPTH]) ^ salt
    dev = np.asarray(_tree_root_fused(jnp.asarray(sampled), _SLICE_DEPTH))
    ok = _be_words_to_bytes(dev) == host_tree_root_words(sampled)
    record(
        "merkle",
        ok,
        None if ok else {"depth": depth, "mode": "salted-slice", "salt": int(salt)},
    )
    return ok


def _spec_shuffled_index(index: int, n: int, seed: bytes, rounds: int) -> int:
    """The per-index swap-or-not loop, straight off the spec text
    (specs/phase0/beacon-chain.md:816-836) — shares nothing with the
    whole-permutation device kernel it cross-checks."""
    sha = hashlib.sha256
    for r in range(rounds):
        pivot = int.from_bytes(sha(seed + bytes([r])).digest()[:8], "little") % n
        flip = (pivot - index) % n
        pos = max(index, flip)
        src = sha(seed + bytes([r]) + (pos // 256).to_bytes(4, "little")).digest()
        if (src[(pos % 256) // 8] >> (pos % 8)) & 1:
            index = flip
    return index


def check_shuffle_slice(perm, n: int, seed: bytes, rounds: int, active=None) -> bool:
    """Sampled lanes of the device permutation vs the per-index spec loop
    (only the sampled lanes cross to the host). With `active`, `perm` is
    the shuffled list of those indices, `active[spec index]` a lane."""
    ok = True
    for i in _sample_rows(n, k=2):
        expect = _spec_shuffled_index(i, n, seed, rounds)
        if active is not None:
            expect = int(active[expect])
        got = int(np.asarray(perm[i]))
        if got != expect:
            ok = False
            record(
                "shuffle",
                False,
                {"lane": i, "expected": expect, "got": got, "n": n},
            )
            break
    if ok:
        record("shuffle", True)
    return ok


def check_bls_item(points, msg: bytes, sig, batch_verdict: bool) -> bool:
    """One sampled (pubkeys, message, aggregate) re-verified through the
    plain host pairing — no device MSM, no routed pairing, no h2g2 cache.
    A True batch verdict must reproduce for every member item."""
    from eth_consensus_specs_tpu.crypto.curve import g1_generator, g1_infinity
    from eth_consensus_specs_tpu.crypto.hash_to_curve import hash_to_g2
    from eth_consensus_specs_tpu.crypto.pairing import pairing_check

    aggpk = g1_infinity()
    for p in points:
        aggpk = aggpk + p
    host_ok = pairing_check(
        [(aggpk, hash_to_g2(bytes(msg))), (-g1_generator(), sig)]
    )
    ok = bool(host_ok) == bool(batch_verdict)
    record(
        "bls_batch",
        ok,
        None if ok else {"batch": batch_verdict, "host": bool(host_ok), "digest": gates.digest(bytes(msg))},
    )
    return ok


def reset_for_tests() -> None:
    with _lock:
        _calls.clear()
