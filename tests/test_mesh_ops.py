"""Mesh-sharded hot-kernel dispatch: bit-parity chips=1 vs chips=N over
the 8-virtual-device CPU mesh conftest.py forces, mesh-aware serve
buckets, signed warmup keys, and the host_local_slice remainder fix.

Cheap parity tests (sum kernels, sharded merkleization, the bisection
path over host pairing) run in tier-1; the scalar-MSM and device-pairing
sharded compiles are minutes on XLA:CPU and ride the nightly slow lane.
"""

import threading

import jax
import numpy as np
import pytest

from eth_consensus_specs_tpu import obs
from eth_consensus_specs_tpu.crypto.curve import g1_generator
from eth_consensus_specs_tpu.ops.g1_msm import (
    many_sum_shape,
    mesh_lane_pad,
    sum_g1_device,
    sum_g1_many_device,
)
from eth_consensus_specs_tpu.ops.merkle import merkleize_many_device
from eth_consensus_specs_tpu.parallel import make_mesh, mesh_ops, multihost
from eth_consensus_specs_tpu.serve import buckets
from eth_consensus_specs_tpu.utils import bls

N_DEVICES = 8
G = g1_generator()


def _mesh(n=N_DEVICES):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices (conftest forces them on CPU)")
    return make_mesh(n)


def _counter(name: str) -> float:
    return obs.snapshot()["counters"].get(name, 0)


# ------------------------------------------------------------- helpers --


def test_mesh_helpers_and_signature():
    mesh = _mesh()
    assert mesh_ops.shard_count(None) == 1
    assert mesh_ops.shard_count(mesh) == N_DEVICES
    sig = mesh_ops.mesh_signature(mesh)
    assert sig == f"cpu{mesh.shape['dp']}x{mesh.shape['sp']}"
    assert mesh_ops.mesh_signature(None) == ""  # single-device keys unsigned
    assert mesh_ops.pad_to_shards(5, 8) == 8
    assert mesh_ops.pad_to_shards(16, 8) == 16


def test_pad_to_shards_degenerate_pads_one_per_shard():
    """shards > items (per-shard count would be 0): every shard still
    gets at least one (padding) item — a zero-extent shard axis is an
    invalid shard_map operand shape, so the floor is `shards`, never 0."""
    assert mesh_ops.pad_to_shards(0, 8) == 8
    assert mesh_ops.pad_to_shards(1, 8) == 8
    assert mesh_ops.pad_to_shards(3, 8) == 8
    for n in range(0, 20):
        padded = mesh_ops.pad_to_shards(n, 8)
        assert padded % 8 == 0 and padded // 8 >= 1  # non-empty shards
        assert padded >= n


def test_mesh_batch_bucket_degenerate_pads_one_per_shard():
    from eth_consensus_specs_tpu.serve import buckets

    cfg = (1, 2, 4, 8, 16, 32, 64)
    # fewer trees than shards: the PER-SHARD count buckets to 1, the
    # dispatch pads to shards x 1 — never an empty shard
    for n in (1, 2, 3, 7):
        assert buckets.mesh_batch_bucket(n, 8, cfg) == 8
    assert buckets.mesh_batch_bucket(0, 8, cfg) == 8
    # and the mesh-aware live key fn agrees with the dispatch padding
    mesh = _mesh()
    key = buckets.merkle_many_key(3, 10, cfg, mesh=mesh)
    assert key[0] == "merkle_many" and key[1] == N_DEVICES
    assert key[3] == mesh_ops.mesh_signature(mesh)
    per_shard = mesh_ops.pad_to_shards(key[1], N_DEVICES) // N_DEVICES
    assert per_shard >= 1


def test_serve_mesh_env_gates(monkeypatch):
    _mesh()
    monkeypatch.setenv("ETH_SPECS_MESH", "0")
    assert mesh_ops.serve_mesh() is None
    monkeypatch.delenv("ETH_SPECS_MESH", raising=False)
    assert mesh_ops.serve_mesh(1) is None  # one chip = single-device path
    m = mesh_ops.serve_mesh(4)
    assert m is not None and mesh_ops.shard_count(m) == 4
    monkeypatch.setenv("ETH_SPECS_SERVE_CHIPS", "2")
    assert mesh_ops.shard_count(mesh_ops.serve_mesh()) == 2


def test_mesh_batch_bucket_per_shard_padding():
    bkts = (1, 2, 4, 8, 16, 32, 64)
    # pow2 shard counts: identical total padding to the global bucket
    assert buckets.mesh_batch_bucket(5, 8, bkts) == 8
    assert buckets.mesh_batch_bucket(20, 8, bkts) == 32
    assert buckets.mesh_batch_bucket(3, 1, bkts) == buckets.batch_bucket(3, bkts)
    # non-pow2 meshes pad strictly less than the global pow2 would
    assert buckets.mesh_batch_bucket(20, 6, bkts) == 24 < buckets.batch_bucket(20, bkts)


def test_many_sum_shape_and_lane_pad():
    assert many_sum_shape(5, 3) == (8, 4)
    assert many_sum_shape(5, 3, shards=8) == (8, 4)  # pow2 shards == global pow2
    assert many_sum_shape(9, 3, shards=6) == (12, 4)  # per-shard pow2, less padding
    assert mesh_lane_pad(10, 1) == 16
    assert mesh_lane_pad(10, 6) == 12


# ------------------------------------------------- sharded merkleization --


def test_merkleize_many_sharded_parity_non_pow2_batch():
    mesh = _mesh()
    rng = np.random.default_rng(11)
    depth = 6
    # 5 trees (non-pow2) with ragged leaf counts: the sharded dispatch
    # pads the tree axis to the mesh, the single-device one to the same
    # pad_batch — roots must be byte-identical
    trees = [
        rng.integers(0, 256, size=(int(rng.integers(1, 65)), 32)).astype(np.uint8)
        for _ in range(5)
    ]
    before = _counter("mesh.dispatches")
    single = merkleize_many_device(trees, depth, pad_batch=8)
    sharded = merkleize_many_device(trees, depth, pad_batch=8, mesh=mesh)
    assert sharded == single
    assert _counter("mesh.dispatches") == before + 1
    # a pad_batch that does not divide the mesh rounds up instead of
    # truncating a shard
    assert merkleize_many_device(trees, depth, pad_batch=5, mesh=mesh) == single


# --------------------------------------------------------- sharded MSM --


def test_sum_g1_many_sharded_parity_ragged_committees():
    mesh = _mesh()
    lists = [[G.mul(13 * i + j + 1) for j in range(3 + (i % 4))] for i in range(6)]
    per_item = [sum_g1_device(pts) for pts in lists]
    assert sum_g1_many_device(lists) == per_item
    assert sum_g1_many_device(lists, mesh=mesh) == per_item


def test_sum_g1_many_handles_infinity_lanes():
    from eth_consensus_specs_tpu.crypto.curve import g1_infinity

    mesh = _mesh()
    lists = [[g1_infinity(), G.mul(7)], [g1_infinity()], [G.mul(5), G.mul(5)]]
    want = [G.mul(7), g1_infinity(), G.mul(10)]
    assert sum_g1_many_device(lists) == want
    assert sum_g1_many_device(lists, mesh=mesh) == want


@pytest.mark.slow
def test_msm_sharded_scalar_parity():
    # the 256-bit double-and-add lanes + cross-shard Jacobian reduction:
    # one heavy shard_map compile — nightly lane
    from eth_consensus_specs_tpu.crypto.msm import msm_g1
    from eth_consensus_specs_tpu.ops.g1_msm import msm_g1_device

    mesh = _mesh()
    pts = [G.mul(i + 2) for i in range(6)]
    ks = [(1 << 63) + 101 * i for i in range(6)]
    assert msm_g1_device(pts, ks, mesh=mesh) == msm_g1_device(pts, ks) == msm_g1(pts, ks)


# --------------------------------------- verify_many over the mesh (RLC) --


def _bls_items(n, committee=3, invalid=()):
    from eth_consensus_specs_tpu.crypto import signature as sig_mod

    sks = list(range(5, 5 + committee))
    pks = [sig_mod.sk_to_pk(sk) for sk in sks]
    msgs = [bytes([m + 1]) * 32 for m in range(3)]
    items = []
    for i in range(n):
        m = msgs[i % len(msgs)]
        sig = bls.Aggregate([bls.Sign(sk, m) for sk in sks])
        if i in invalid:
            sig = b"\x01" + bytes(sig)[1:]
        items.append((pks, m, bytes(sig)))
    return items


def test_verify_many_mesh_bisection_bit_identical():
    """The serving batch entry point over the mesh: sharded per-item G1
    terms, host pairing, invalid items exercising the bisection —
    verdicts must be bit-identical to the single-device path and to
    direct singleton calls. `verify_many` reads no backend switch: the
    mesh handed in is what shards the sums."""
    from eth_consensus_specs_tpu.ops import bls_batch

    mesh = _mesh()
    prior_active = bls.bls_active
    bls.bls_active = True
    try:
        items = _bls_items(7, invalid={2, 5})
        direct = [bls_batch.batch_verify_aggregates([it]) for it in items]
        assert direct == [i not in {2, 5} for i in range(7)]
        assert bls_batch.verify_many(items) == direct
        before = _counter("mesh.dispatches")
        assert bls_batch.verify_many(items, mesh=mesh) == direct
        assert _counter("mesh.dispatches") > before
    finally:
        bls.bls_active = prior_active


@pytest.mark.slow
def test_verify_many_sharded_pairing_bisection():
    """Full sharded path: per-shard partial Miller products + psum-style
    Fq12 combine, reached through the `bls.use_tpu()` backend on
    `batch_verify_aggregates(..., mesh=mesh)`, each item alone as a
    bisection's last step checks it — minutes of XLA:CPU compile, nightly
    lane."""
    from eth_consensus_specs_tpu.ops import bls_batch

    mesh = _mesh(2)
    items = _bls_items(5, invalid={3})
    prior_active, prior_backend = bls.bls_active, bls.backend_name()
    bls.bls_active = True
    bls.use_tpu()
    try:
        sharded = [bls_batch.batch_verify_aggregates([it], mesh=mesh) for it in items]
    finally:
        bls.bls_active = prior_active
        if prior_backend == "pyspec":
            bls.use_pyspec()
    assert sharded == [i != 3 for i in range(5)]
    assert sharded == bls_batch.verify_many(items)


# ------------------------------------------- serve buckets + warmup keys --


def test_mesh_signed_warmup_keys_roundtrip(tmp_path, monkeypatch):
    mesh = _mesh()
    sig = mesh_ops.mesh_signature(mesh)
    monkeypatch.setattr(buckets, "_SEEN_SHAPES", set())
    assert buckets.note_dispatch("merkle_many", 8, 4, sig) is True
    assert buckets.note_dispatch("merkle_many", 8, 4, sig) is False  # dedupes
    assert buckets.note_dispatch("merkle_many", 8, 4) is True  # unsigned differs
    path = str(tmp_path / "warm.jsonl")
    buckets.write_warmup(path)
    keys = buckets.load_warmup(path)
    assert ("merkle_many", 8, 4, sig) in keys and ("merkle_many", 8, 4) in keys


def test_precompile_skips_alien_mesh_signature(tmp_path, monkeypatch):
    _mesh()
    monkeypatch.setattr(buckets, "_SEEN_SHAPES", set())
    # a key signed by a mesh this process is not running must be skipped,
    # not compiled wrong
    warmed = buckets.precompile([("merkle_many", 8, 4, "tpu64x2")])
    assert warmed == 0
    events = [
        e for e in obs.get_registry().events if e.get("kind") == "serve.precompile_skipped"
    ]
    assert events and events[-1]["reason"] == "mesh-signature mismatch"


def test_precompile_replays_current_mesh_signature(monkeypatch):
    mesh = _mesh()
    sig = mesh_ops.mesh_signature(mesh)
    monkeypatch.setattr(buckets, "_SEEN_SHAPES", set())
    before = _counter("serve.compiles")
    assert buckets.precompile([("merkle_many", 8, 4, sig)]) == 1
    assert _counter("serve.compiles") == before + 1
    # the replayed shape is now warm: the real dispatch pays no compile
    assert buckets.note_dispatch("merkle_many", 8, 4, sig) is False


# ------------------------------------------------- service end to end --


def test_mesh_dispatch_worthwhile_crossover():
    # pinned like the device/host crossover: toy flushes stay on the
    # single-device path, bucket-sized ones shard
    assert not buckets.mesh_dispatch_worthwhile(1 << 6, trees=8)  # 512 chunks
    assert buckets.mesh_dispatch_worthwhile(1 << 10, trees=8)
    assert buckets.MESH_SUBTREE_THRESHOLD == 2048


def test_service_mesh_dispatch_end_to_end(monkeypatch):
    from eth_consensus_specs_tpu import serve
    from eth_consensus_specs_tpu.ops.merkle import merkleize_subtree_device
    from eth_consensus_specs_tpu.serve.config import ServeConfig

    _mesh()
    # depth-4 toy trees sit below the mesh crossover; force the sharded
    # path so the test exercises it without bucket-sized compiles
    monkeypatch.setattr(buckets, "MESH_SUBTREE_THRESHOLD", 0)
    rng = np.random.default_rng(3)
    depth = 4
    # leaf counts in (2**(d-1), 2**d] so every request lands at depth 4
    # (submit_hash_tree_root derives depth per tree) and one flush
    # co-batches all eight
    trees = [
        rng.integers(0, 256, size=(int(rng.integers(9, 17)), 32)).astype(np.uint8)
        for _ in range(8)
    ]
    direct = [merkleize_subtree_device(t, depth) for t in trees]
    cfg = ServeConfig(
        max_batch=8, max_wait_ms=100.0, buckets=(1, 2, 4, 8), mesh_chips=N_DEVICES
    )
    before = _counter("mesh.dispatches")
    with serve.VerifyService(cfg, name="mesh-test") as svc:
        futs = [svc.submit_hash_tree_root(t) for t in trees]
        got = [f.result(timeout=60) for f in futs]
    assert got == direct
    assert _counter("mesh.dispatches") > before
    sig = mesh_ops.mesh_signature(mesh_ops.serve_mesh(N_DEVICES))
    signed = [k for k in buckets.seen_shapes() if k[0] == "merkle_many" and sig in k]
    assert signed, f"no mesh-signed merkle_many compile key in {buckets.seen_shapes()}"


def test_service_mesh_chips_one_stays_single_device():
    from eth_consensus_specs_tpu import serve
    from eth_consensus_specs_tpu.ops.merkle import merkleize_subtree_device
    from eth_consensus_specs_tpu.serve.config import ServeConfig

    _mesh()
    rng = np.random.default_rng(4)
    depth = 4
    trees = [rng.integers(0, 256, size=(16, 32)).astype(np.uint8) for _ in range(4)]
    direct = [merkleize_subtree_device(t, depth) for t in trees]
    before = _counter("mesh.dispatches")
    cfg = ServeConfig(max_batch=4, max_wait_ms=50.0, buckets=(1, 2, 4), mesh_chips=1)
    with serve.VerifyService(cfg, name="mesh1-test") as svc:
        futs = [svc.submit_hash_tree_root(t) for t in trees]
        assert [f.result(timeout=60) for f in futs] == direct
    assert _counter("mesh.dispatches") == before  # single-device path


# --------------------------------------------------- host_local_slice --


def test_host_local_slice_remainder_raises_typed_and_counts():
    mesh = _mesh()
    before = _counter("multihost.slice_remainder")
    with pytest.raises(multihost.ShardRemainderError) as ei:
        multihost.host_local_slice(mesh, 1027)
    assert ei.value.remainder == 1027 % 8
    assert _counter("multihost.slice_remainder") == before + 1027 % 8


def test_host_local_slice_pad_covers_every_row():
    mesh = _mesh()
    lo, hi = multihost.host_local_slice(mesh, 1027, pad=True)
    padded = multihost.padded_global(1027, 8)
    assert padded == 1032
    # single process owns the whole padded domain — nothing truncated
    assert (lo, hi) == (0, padded)
    # divisible splits are untouched by the fix
    assert multihost.host_local_slice(mesh, 1024) == (0, 1024)


def test_sharded_dispatch_thread_safety():
    """Two threads racing the same sharded entry must both get correct
    roots (the per-(mesh, depth) fn cache is shared)."""
    mesh = _mesh()
    rng = np.random.default_rng(9)
    depth = 5
    trees = [rng.integers(0, 256, size=(32, 32)).astype(np.uint8) for _ in range(8)]
    want = merkleize_many_device(trees, depth, pad_batch=8)
    results = [None, None]

    def run(i):
        results[i] = merkleize_many_device(trees, depth, pad_batch=8, mesh=mesh)

    ts = [threading.Thread(target=run, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert results[0] == want and results[1] == want
