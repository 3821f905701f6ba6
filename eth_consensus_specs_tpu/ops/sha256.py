"""Vectorized SHA-256 for SSZ merkleization.

The merkle workload is millions of *independent* 64-byte messages
(left||right child pairs), each hashed with the same fixed schedule: one
compression over the data block + one over the constant padding block. That
is a pure SIMD problem — no data-dependent control flow — so the kernel is
written with the 128 rounds fully UNROLLED over a batch axis: XLA fuses the
whole round chain into one VPU kernel that reads each message once from HBM
and writes each digest once (measured ~2.9 Ghash/s on v5e at 256k batch,
~3000x hashlib's per-node loop). A scan-based variant was tried first and
ran *slower than hashlib* on TPU because the carry round-tripped HBM every
round — unrolling is what makes this kernel a kernel.

Compile cost of the unrolled graph (~10s) is contained by dispatching in
FIXED tile sizes (two shapes process-wide), not per-batch-size buckets.

Replaces the reference's per-node `hashlib.sha256` C calls
(reference: tests/core/pyspec/eth2spec/utils/hash_function.py:8-9).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from eth_consensus_specs_tpu import obs
from eth_consensus_specs_tpu.obs import watchdog, xprof

_K = np.array(
    [
        0x428A2F98, 0x71374491, 0xB5C0FBCF, 0xE9B5DBA5, 0x3956C25B, 0x59F111F1,
        0x923F82A4, 0xAB1C5ED5, 0xD807AA98, 0x12835B01, 0x243185BE, 0x550C7DC3,
        0x72BE5D74, 0x80DEB1FE, 0x9BDC06A7, 0xC19BF174, 0xE49B69C1, 0xEFBE4786,
        0x0FC19DC6, 0x240CA1CC, 0x2DE92C6F, 0x4A7484AA, 0x5CB0A9DC, 0x76F988DA,
        0x983E5152, 0xA831C66D, 0xB00327C8, 0xBF597FC7, 0xC6E00BF3, 0xD5A79147,
        0x06CA6351, 0x14292967, 0x27B70A85, 0x2E1B2138, 0x4D2C6DFC, 0x53380D13,
        0x650A7354, 0x766A0ABB, 0x81C2C92E, 0x92722C85, 0xA2BFE8A1, 0xA81A664B,
        0xC24B8B70, 0xC76C51A3, 0xD192E819, 0xD6990624, 0xF40E3585, 0x106AA070,
        0x19A4C116, 0x1E376C08, 0x2748774C, 0x34B0BCB5, 0x391C0CB3, 0x4ED8AA4A,
        0x5B9CCA4F, 0x682E6FF3, 0x748F82EE, 0x78A5636F, 0x84C87814, 0x8CC70208,
        0x90BEFFFA, 0xA4506CEB, 0xBEF9A3F7, 0xC67178F2,
    ],
    dtype=np.uint32,
)

_IV = np.array(
    [0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
     0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19],
    dtype=np.uint32,
)

# Message-schedule words of the constant second block for a 64-byte message:
# 0x80 delimiter, zeros, bit-length 512 in the last word.
_PAD_BLOCK = np.zeros(16, dtype=np.uint32)
_PAD_BLOCK[0] = 0x80000000
_PAD_BLOCK[15] = 512


def _rotr(x, n: int):
    return (x >> n) | (x << (32 - n))


def _compress(state: list, w: list) -> list:
    """One SHA-256 compression, rounds unrolled.

    state: 8 uint32 arrays, w: 16 uint32 arrays, all sharing a batch shape.
    """
    ws = list(w)
    for t in range(16, 64):
        s0 = _rotr(ws[t - 15], 7) ^ _rotr(ws[t - 15], 18) ^ (ws[t - 15] >> 3)
        s1 = _rotr(ws[t - 2], 17) ^ _rotr(ws[t - 2], 19) ^ (ws[t - 2] >> 10)
        ws.append(ws[t - 16] + s0 + ws[t - 7] + s1)
    a, b, c, d, e, f, g, h = state
    for t in range(64):
        S1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + S1 + ch + jnp.uint32(_K[t]) + ws[t]
        S0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        h, g, f, e, d, c, b, a = g, f, e, d + t1, c, b, a, t1 + S0 + maj
    return [s + o for s, o in zip(state, [a, b, c, d, e, f, g, h])]


def _compress_scan(state8: jnp.ndarray, w16: jnp.ndarray) -> jnp.ndarray:
    """One compression as a lax.scan over the 64 rounds.

    state8: uint32[8, N], w16: uint32[16, N]. The rolling 16-word message-
    schedule window rides in the carry: W[t+16] = W[t] + s0(W[t+1]) +
    W[t+9] + s1(W[t+14]). Semantically identical to the unrolled form; the
    graph is ~100x smaller. XLA:CPU chokes for minutes on the unrolled
    graph, so this is the CPU (test/virtual-mesh) form — TPU keeps the
    unrolled one, where the fused round chain is the whole point.
    """

    def rnd(carry, k):
        a, b, c, d, e, f, g, h, win = carry
        wt = win[0]
        S1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + S1 + ch + k + wt
        S0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        s0 = _rotr(win[1], 7) ^ _rotr(win[1], 18) ^ (win[1] >> 3)
        s1 = _rotr(win[14], 17) ^ _rotr(win[14], 19) ^ (win[14] >> 10)
        wnext = win[0] + s0 + win[9] + s1
        win = jnp.concatenate([win[1:], wnext[None]], axis=0)
        return (t1 + S0 + maj, a, b, c, d + t1, e, f, g, win), None

    init = tuple(state8[i] for i in range(8)) + (w16,)
    (a, b, c, d, e, f, g, h, _), _ = jax.lax.scan(rnd, init, jnp.asarray(_K))
    out = jnp.stack([a, b, c, d, e, f, g, h])
    return state8 + out


def sha256_pair_words_scan(words: jnp.ndarray) -> jnp.ndarray:
    """Scan-form batch hash: uint32[N, 16] -> uint32[N, 8]."""
    n = words.shape[0]
    state = jnp.broadcast_to(jnp.asarray(_IV)[:, None], (8, n))
    state = _compress_scan(state, words.T)
    pad = jnp.broadcast_to(jnp.asarray(_PAD_BLOCK)[:, None], (16, n))
    state = _compress_scan(state, pad)
    return state.T


def _own_fusion(digest: jnp.ndarray) -> jnp.ndarray:
    """Keep one unrolled hash ONE fusion. Left alone XLA fuses a hash
    into the hash that consumes it (a validator leaf is a chain of
    three, a fold a chain of dozens) and the compile time of the fused
    kernel grows far faster than its length: compiled for a v5e, one
    hash of u32[4096, 16] takes 5 s, a chain of two 131 s, the
    three-hash validator leaf 171 s. The barrier costs the digest one
    trip through HBM, which the kernel's traffic model already counts."""
    return jax.lax.optimization_barrier(digest)


def sha256_pair_words_unrolled(words: jnp.ndarray) -> jnp.ndarray:
    """Unrolled batch hash: uint32[N, 16] -> uint32[N, 8]."""
    n = words.shape[0]
    w = [words[:, i] for i in range(16)]
    state = [jnp.broadcast_to(jnp.uint32(_IV[i]), (n,)) for i in range(8)]
    state = _compress(state, w)
    pad = [jnp.broadcast_to(jnp.uint32(_PAD_BLOCK[i]), (n,)) for i in range(16)]
    state = _compress(state, pad)
    return _own_fusion(jnp.stack(state, axis=-1))


def sha256_single_block(words: jnp.ndarray) -> jnp.ndarray:
    """Hash a batch of messages that fit one fully-padded block.

    words: uint32[N, 16] (padding already applied by the caller) ->
    uint32[N, 8]. One compression instead of sha256_pair_words' two —
    the shape of the shuffle's decision-bit hashes (33/37-byte messages,
    specs/phase0/beacon-chain.md:816-836)."""
    n = words.shape[0]
    if _round_scan(n):
        state = jnp.broadcast_to(jnp.asarray(_IV)[:, None], (8, n))
        return _compress_scan(state, words.T).T
    w = [words[:, i] for i in range(16)]
    state = [jnp.broadcast_to(jnp.uint32(_IV[i]), (n,)) for i in range(8)]
    return _own_fusion(jnp.stack(_compress(state, w), axis=-1))


def sha256_pair_words(words: jnp.ndarray) -> jnp.ndarray:
    """Hash a batch of 64-byte messages given as big-endian words.

    words: uint32[N, 16] -> uint32[N, 8]. Jit-traceable (inline this into
    larger fused kernels; for standalone use go through sha256_tiled).
    Picks the graph shape per backend: fully unrolled rounds on
    accelerators (XLA fuses the whole chain; scan carries round-trip HBM),
    round-scan on CPU (the unrolled graph takes minutes in XLA:CPU).
    """
    if _round_scan(words.shape[0]):
        return sha256_pair_words_scan(words)
    return sha256_pair_words_unrolled(words)


# Up to this many messages a call, an accelerator takes the round scan
# too. Unrolling buys bandwidth: the scan's carry makes a round trip a
# round, which for a batch this small is a few KB. What it costs is the
# same whatever the batch: ~5 s of compile for each call site, compiled
# for a v5e, against 0.1 to 0.4 s for the scan body (PERF.md, PR 22) —
# and a state root is mostly SMALL hashes by count: three checkpoints,
# the top combine, the narrow levels of every tree. The one small-batch
# caller that takes the unrolled body all the same is the list tails'
# chain (ops/state_root.list_roots), by its own choice: ONE call site a
# program, run some twenty times in sequence, where the scan's 128 loop
# trips a hash were 61 % of a 2^20 state root (PERF.md, PR 26 and 28).
SMALL_BATCH = 64


def _round_scan(n_messages: int) -> bool:
    return jax.default_backend() == "cpu" or n_messages <= SMALL_BATCH


_kernel = jax.jit(sha256_pair_words)

# Fixed dispatch tiles: exactly these shapes ever compile (one-time ~10s
# each on TPU). Large tile amortizes dispatch; small tile bounds padding
# waste on shallow tree levels.
TILES = (65536, 2048)


def sha256_tiled(pairs: jnp.ndarray) -> jnp.ndarray:
    """Hash M pairs on device. pairs: uint32[M, 16] -> uint32[M, 8].

    Host-side greedy tiling over the fixed shapes; data stays on device.
    """
    m = pairs.shape[0]
    used_tiles: set[int] = set()
    # 64B message read + 32B digest write per hash: the traffic the span's
    # roofline verdict is judged against
    with obs.span("sha256.tiled", work_bytes=96 * m, messages=m) as sp:
        outs = []
        dispatches = 0
        pos = 0
        while pos < m:
            rest = m - pos
            tile = next((t for t in TILES if rest >= t), None)
            if tile is None:
                tile = TILES[-1]
                pad = jnp.zeros((tile - rest, 16), dtype=jnp.uint32)
                outs.append(_kernel(jnp.concatenate([pairs[pos:], pad], axis=0))[:rest])
                pos = m
            else:
                outs.append(_kernel(pairs[pos : pos + tile]))
                pos += tile
            used_tiles.add(tile)
            dispatches += 1
        out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
        sp.result = out
    if xprof.enabled():
        # XLA-derived attribution once per tile shape: compile timing,
        # flops/bytes/memory gauges, and the bytes floor cross-check
        # against the same 96 B/hash model the span above declared
        for t in sorted(used_tiles):
            xprof.analyze(
                "sha256",
                _kernel,
                (jax.ShapeDtypeStruct((t, 16), jnp.uint32),),
                hand_bytes=96 * t,
                dims=(t,),
            )
    obs.count("sha256.compressions", 2 * m)  # data block + constant padding block
    obs.count("sha256.messages", m)
    obs.count("sha256.dispatches", dispatches)
    if watchdog.should_check("sha256"):
        watchdog.check_sha256_slice(pairs, out)
    return out


def sha256_64B_batch_np(pairs: np.ndarray) -> np.ndarray:
    """Host-convenience entry: uint8[N, 64] -> uint8[N, 32]."""
    n = pairs.shape[0]
    words = np.ascontiguousarray(pairs).view(">u4").astype(np.uint32).reshape(n, 16)
    digest_words = np.asarray(sha256_tiled(jnp.asarray(words)))
    return digest_words.astype(">u4", order="C").view(np.uint8).reshape(n, 32)


def sha256_oracle(msg: bytes) -> bytes:
    """Single-message oracle path through the kernel (64-byte messages only),
    for correctness tests against hashlib."""
    assert len(msg) == 64
    out = sha256_64B_batch_np(np.frombuffer(msg, dtype=np.uint8).reshape(1, 64))
    return out[0].tobytes()
