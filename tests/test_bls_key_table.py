"""The registry's key table (ops/key_table.py) and the served BLS path on
it: verdicts equal the pairing oracle's with the keys resident, the bytes
form and the index form of a request agree, a key that fails KeyValidate is
refused at registration, and a registry that cycles is decoded once."""

from __future__ import annotations

import hashlib

import numpy as np
import pytest

from eth_consensus_specs_tpu import obs
from eth_consensus_specs_tpu.crypto import signature
from eth_consensus_specs_tpu.crypto.curve import g1_from_bytes, g1_generator
from eth_consensus_specs_tpu.crypto.fields import R
from eth_consensus_specs_tpu.ops import bls_batch, g1_msm
from eth_consensus_specs_tpu.ops.field_limbs import to_mont
from eth_consensus_specs_tpu.ops.key_table import KeyTable
from eth_consensus_specs_tpu.serve import buckets
from eth_consensus_specs_tpu.serve.config import ServeConfig
from eth_consensus_specs_tpu.serve.service import VerifyService

BASE = 0x5EED0000
N_KEYS = 24
INFINITY = bytes([0xC0]) + bytes(47)


def message(tag: int) -> bytes:
    return hashlib.sha256(bytes([tag])).digest()


@pytest.fixture(scope="module")
def registry():
    return [signature.sk_to_pk(BASE + v) for v in range(N_KEYS)]


def aggregate(signers, tag: int) -> bytes:
    return signature.sign(sum(BASE + int(v) for v in signers) % R, message(tag))


def decode_samples() -> int:
    hist = obs.histogram("bls.key_decode_ms")
    return hist.count if hist is not None else 0


@pytest.fixture
def service(registry):
    svc = VerifyService(ServeConfig(max_batch=8, max_wait_ms=20, mesh_chips=1), name="keys")
    yield svc
    svc.close()


# ------------------------------------------------------------- the table --


def test_the_table_keeps_points_limbs_and_the_way_from_a_key_to_its_index(registry):
    table = KeyTable(registry)
    assert len(table) == N_KEYS
    assert table.points(np.array([0, 23])) == [g1_from_bytes(registry[v]) for v in (0, 23)]
    x, y = (np.asarray(a) for a in table.device_limbs())
    for v in (0, 7, 23):
        p = g1_from_bytes(registry[v])
        assert (x[v] == to_mont(p.x.n)).all() and (y[v] == to_mont(p.y.n)).all()
    assert table.resolve(registry[3:6]).tolist() == [3, 4, 5]
    assert table.resolve(np.array([5, 23])).tolist() == [5, 23]
    assert table.resolve([signature.sk_to_pk(1)]) is None  # not of the registry
    assert table.resolve(np.array([0, N_KEYS])) is None and table.resolve(np.array([-1])) is None


def _outside_the_subgroup() -> bytes:
    x = 1
    while True:
        key = bytes([0x80]) + x.to_bytes(48, "big")[1:]
        try:
            g1_from_bytes(key, subgroup_check=False)
        except ValueError:
            x += 1
            continue
        try:
            g1_from_bytes(key)
        except ValueError:
            return key
        x += 1


@pytest.mark.parametrize("bad", ["infinity", "off_the_curve", "outside_the_subgroup",
                                 "uncompressed_flag", "short"])
def test_a_key_that_fails_key_validate_is_refused_at_registration(registry, service, bad):
    key = {
        "infinity": INFINITY,
        "off_the_curve": bytes([0x80]) + bytes(46) + b"\x05",
        "outside_the_subgroup": _outside_the_subgroup(),
        "uncompressed_flag": bytes([registry[0][0] & 0x7F]) + registry[0][1:],
        "short": registry[0][:47],
    }[bad]
    assert not signature.key_validate(key)
    with pytest.raises(ValueError):
        service.register_pubkeys(registry[:5] + [key] + registry[5:])
    assert service._keys is None  # nothing is kept of a registry with a bad key
    with pytest.raises(ValueError):
        service.submit_bls_aggregate(np.array([0]), message(0), aggregate([0], 0))


@pytest.mark.parametrize("strip", [4, 2])
def test_the_gather_and_sum_program_equals_the_host_sums(registry, strip):
    table = KeyTable(registry)
    # a key twice: a doubling
    rows = [np.array([0, 1, 2], np.int32), np.array([5, 5, 9, 23], np.int32)]
    index = np.full((2, 4), -1, np.int32)
    for i, row in enumerate(rows):
        index[i, : len(row)] = row
    out = g1_msm.sum_indexed_kernel(*table.device_limbs(), index, strip=strip)
    got = g1_msm._jacobian_to_points(*out)
    g = g1_generator()
    assert got == [g.mul(3 * BASE + 3), g.mul(4 * BASE + 42)]


# ------------------------------------------------------- the served path --


def flush_of_cases(registry):
    """(signers, message, signature) and what each case is."""
    cases = {
        "valid": ([1, 2, 3, 4], 1, None),
        "valid_one_signer": ([9], 2, None),
        "another_aggregates_signature": ([5, 6, 7], 3, aggregate([5, 6, 7], 99)),
        "a_signer_left_out": ([10, 11], 4, aggregate([10, 11, 12], 4)),
        "malformed_signature": ([13, 14], 5, b"\x00" * 96),
        "valid_again": ([20, 21, 22, 23], 6, None),
    }
    return {name: (np.array(s), message(tag), sig or aggregate(s, tag))
            for name, (s, tag, sig) in cases.items()}


def test_verdicts_equal_the_pairing_oracle_in_both_forms_with_the_keys_resident(registry, service):
    service.register_pubkeys(registry)
    cases = flush_of_cases(registry)
    before = decode_samples()
    by_index = [service.submit_bls_aggregate(s, m, sig) for s, m, sig in cases.values()]
    by_bytes = [service.submit_bls_aggregate([registry[v] for v in s], m, sig)
                for s, m, sig in cases.values()]
    want = [signature.fast_aggregate_verify([registry[v] for v in s], m, sig)
            for s, m, sig in cases.values()]
    assert want == [name.startswith("valid") for name in cases]
    assert [f.result(120) for f in by_index] == want
    assert [f.result(120) for f in by_bytes] == want
    assert decode_samples() == before  # every key answered from the table


def test_a_key_outside_the_registry_is_decoded_as_before(registry, service):
    service.register_pubkeys(registry[:8])
    signers = [2, 12]  # the second is not registered
    sig = aggregate(signers, 7)
    before = decode_samples()
    keys = [registry[v] for v in signers]
    assert service.submit_bls_aggregate(keys, message(7), sig).result(120) is True
    assert service.submit_bls_aggregate(keys[:1], message(7), sig).result(120) is False
    with_infinity = service.submit_bls_aggregate([registry[2], INFINITY], message(7), sig)
    assert with_infinity.result(120) is False
    assert decode_samples() > before


def test_a_registry_larger_than_a_flush_cycled_twice_is_decoded_once(registry, service):
    """Without a table the key cache holds the registry: no clear-all."""
    signature._PK_CACHE.clear()
    flushes = [list(range(at, at + 8)) for at in range(0, N_KEYS, 8)]

    def cycle(tag: int) -> int:
        before = decode_samples()
        for n, signers in enumerate(flushes):
            sig = aggregate(signers, tag + n)
            keys = [registry[v] for v in signers]
            assert service.submit_bls_aggregate(keys, message(tag + n), sig).result(120) is True
        return decode_samples() - before

    assert cycle(30) == len(flushes)
    assert cycle(40) == 0
    service.register_pubkeys(registry)
    signature._PK_CACHE.clear()
    assert cycle(50) == 0  # and with the registry handed over, never


def test_a_full_key_cache_drops_its_oldest_key_and_never_all(registry, monkeypatch):
    monkeypatch.setattr(signature, "_PK_CACHE_MAX", 4)
    signature._PK_CACHE.clear()
    for key in registry[:6]:
        signature._load_pk(key)
    assert list(signature._PK_CACHE) == registry[2:6]


def mixed_flush(registry):
    """The cases by index, one item in the bytes form and one with a key
    of its own; what each verdict has to be."""
    cases = flush_of_cases(registry)
    items = list(cases.values())
    items[1] = ([registry[9]], items[1][1], items[1][2])
    outsider = [registry[0], signature.sk_to_pk(77)]
    items.append((outsider, message(8), signature.sign((BASE + 77) % R, message(8))))
    return items, [name.startswith("valid") for name in cases] + [True]


def test_only_a_precompiled_bucket_sums_on_the_device(registry, service, monkeypatch):
    """A flush never compiles the gather-and-sum program on the serving
    thread: until `precompile` has warmed its (items, lanes) bucket the
    sums go through the core; afterwards that bucket's go to the device,
    any other bucket's still through the core."""
    buckets.reset_for_tests()
    service.register_pubkeys(registry)
    table = service._keys
    items, want = mixed_flush(registry)
    calls = []
    real = g1_msm.sum_indexed_device
    monkeypatch.setattr(g1_msm, "sum_indexed_device",
                        lambda *a, **k: calls.append(a[2]) or real(*a, **k))
    assert bls_batch.verify_many(items, keys=table) == want
    assert calls == []
    assert service.precompile([("bls_keysum", 8, 4, N_KEYS)]) == 1
    assert service.precompile([("bls_keysum", 8, 4, N_KEYS + 1)]) == 0  # another registry's program
    assert calls == [(8, 4)]
    assert bls_batch.verify_many(items, keys=table) == want
    assert calls == [(8, 4)] * 2  # six indexed items of up to four keys, one dispatch
    assert bls_batch.verify_many(items[:2], keys=table) == want[:2]  # the (2, 4) bucket: the core
    assert calls == [(8, 4)] * 2


def test_over_a_mesh_the_sums_shard_their_items_with_the_table_replicated(registry):
    import jax

    from eth_consensus_specs_tpu.parallel import make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices (conftest forces them on CPU)")
    mesh = make_mesh(8)
    table = KeyTable(registry)
    items, want = mixed_flush(registry)
    before = obs.snapshot()["counters"].get("mesh.dispatches", 0)
    assert bls_batch.verify_many(items, mesh=mesh, keys=table) == want
    # the table's items in one sharded dispatch, the two loose ones in another
    assert obs.snapshot()["counters"]["mesh.dispatches"] == before + 2
    assert bls_batch.verify_many(items, keys=table) == want
