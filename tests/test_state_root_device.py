"""Full-state device merkleization (ops/state_root.py via
parallel/resident.py) vs ssz.hash_tree_root on the equivalently-updated
object state — SURVEY hard part 3's bit-exactness gate."""

import pytest

# full-state root compiles are minutes-scale — nightly/full lane (make test-full)
pytestmark = pytest.mark.slow

import numpy as np

from eth_consensus_specs_tpu import ssz
from eth_consensus_specs_tpu.parallel import resident
from eth_consensus_specs_tpu.test_infra.attestations import next_epoch_with_attestations
from eth_consensus_specs_tpu.test_infra.context import spec_state_test, with_phases


def _root_bytes(acc) -> bytes:
    return np.asarray(acc).astype(">u4", order="C").view(np.uint8).tobytes()


def _to_boundary(spec, state):
    from eth_consensus_specs_tpu.test_infra.state import next_slots

    boundary = int(state.slot) + (
        spec.SLOTS_PER_EPOCH - int(state.slot) % spec.SLOTS_PER_EPOCH
    )
    if int(state.slot) < boundary - 1:
        next_slots(spec, state, boundary - 1 - int(state.slot))


def _device_vs_object(spec, state, with_root="state"):
    _to_boundary(spec, state)
    cols, just, static = resident.ingest_full(spec, state)
    carry = resident.run_epochs(spec, cols, just, 1, with_root=with_root, static=static)
    device_root = _root_bytes(carry.root_acc)

    expected = state.copy()
    old_current = list(expected.current_epoch_participation)
    resident.writeback(spec, expected, carry)
    # the accounting epoch's participation rotation
    part_t = type(expected.current_epoch_participation)
    expected.previous_epoch_participation = part_t(old_current)
    expected.current_epoch_participation = part_t([0] * len(old_current))
    assert bytes(ssz.hash_tree_root(expected)) == device_root


@with_phases(["altair", "deneb"])
@spec_state_test
def test_state_root_genesis_epoch(spec, state):
    _device_vs_object(spec, state)


@with_phases(["altair", "deneb"])
@spec_state_test
def test_state_root_after_participation(spec, state):
    next_epoch_with_attestations(spec, state, fill_cur_epoch=False, fill_prev_epoch=True)
    # dirty some balances/validators so every dynamic subtree moves
    for i in range(0, len(state.validators), 3):
        state.balances[i] = int(state.balances[i]) - 12345
    state.validators[2].slashed = True
    _device_vs_object(spec, state)


@with_phases(["altair", "deneb"])
@spec_state_test
def test_state_root_incremental_vs_object_tree(spec, state):
    """The merkle_inc forest path against ssz.hash_tree_root on the
    equivalently-updated object state — the incremental root is the
    OBJECT tree's root after writeback, not merely the full device
    path's (which tests/test_resident.py already pins it to)."""
    next_epoch_with_attestations(spec, state, fill_cur_epoch=False, fill_prev_epoch=True)
    for i in range(0, len(state.validators), 3):
        state.balances[i] = int(state.balances[i]) - 12345
    state.validators[2].slashed = True
    _device_vs_object(spec, state, with_root="state_inc")


@with_phases(["altair"])
@spec_state_test
def test_state_root_multi_epoch_chain(spec, state):
    """Three chained epochs: the xor-accumulated roots must equal the
    xor of three independently computed object roots is impractical to
    reconstruct midway, so instead run 1 epoch twice from the same state
    and check determinism + non-triviality."""
    _to_boundary(spec, state)
    cols, just, static = resident.ingest_full(spec, state)
    c1 = resident.run_epochs(spec, cols, just, 1, with_root="state", static=static)
    c2 = resident.run_epochs(spec, cols, just, 1, with_root="state", static=static)
    assert _root_bytes(c1.root_acc) == _root_bytes(c2.root_acc)
    assert _root_bytes(c1.root_acc) != b"\x00" * 32
    c3 = resident.run_epochs(spec, cols, just, 3, with_root="state", static=static)
    assert _root_bytes(c3.root_acc) != _root_bytes(c1.root_acc)


# ------------------------------------------------ the list tails as lanes --

# which lists ride the fold, by program
_LANE_SHAPES = {
    2: ("validators", "balances"),  # a phase0 state: no scores, no participation lists
    3: ("validators", "balances", "inactivity_scores"),  # the forest programs
    4: ("validators", "balances", "inactivity_scores", "previous_epoch_participation"),
}


def _tail_shape(name: str, n: int) -> tuple[int, int]:
    from eth_consensus_specs_tpu.ops import state_root as sr

    per_chunk, limit = {
        "validators": (1, sr.VALIDATOR_REGISTRY_LIMIT_LOG2),
        "balances": (4, sr.BALANCE_LIMIT_CHUNKS_LOG2),
        "inactivity_scores": (4, sr.BALANCE_LIMIT_CHUNKS_LOG2),
        "previous_epoch_participation": (32, sr.PARTICIPATION_LIMIT_CHUNKS_LOG2),
    }[name]
    chunks = -(-n // per_chunk)
    return max(chunks - 1, 0).bit_length(), limit


@pytest.mark.parametrize("lanes", [2, 3, 4])
@pytest.mark.parametrize("n", [1, 2, 5, 16, 17, 64, 100, 1000])
def test_list_roots_lanes_match_the_host_fold(n, lanes):
    """Every lane of the one fold against the host oracle's fold and
    length mix of the same subtree root. At 16 validators and below a
    packed list is one chunk and its chain is longer than the
    registry's, so lanes enter at different steps; from 17 they are
    equally long."""
    import jax
    import jax.numpy as jnp

    from eth_consensus_specs_tpu.ops import state_root as sr
    from eth_consensus_specs_tpu.ops import state_root_host as host

    zh = sr.zerohash_words(41)
    rng = np.random.default_rng(n * 8 + lanes)
    subs = rng.integers(0, 2**32, size=(lanes, 8), dtype=np.uint64).astype(np.uint32)
    shapes = [_tail_shape(name, n) for name in _LANE_SHAPES[lanes]]
    tails = {
        name: sr.ListTail(jnp.asarray(sub), depth, limit, n)
        for name, sub, (depth, limit) in zip(_LANE_SHAPES[lanes], subs, shapes)
    }
    longest = max(limit - depth for depth, limit in shapes)
    assert sr.list_tail_steps(tails.values()) == longest + 1
    ragged = len({limit - depth for depth, limit in shapes}) > 1
    assert not ragged if n >= 17 else ragged or lanes < 4

    roots = jax.jit(lambda z: sr.list_roots(tails, z))(jnp.asarray(zh))
    assert set(roots) == set(tails)
    for name, sub, (depth, limit) in zip(_LANE_SHAPES[lanes], subs, shapes):
        expected = host.mix_length_np(host.fold_to_limit_np(sub, depth, limit, zh), n)
        assert _root_bytes(roots[name]) == _root_bytes(expected), name


@pytest.fixture(scope="module")
def altair_64():
    """(spec, state) of a 64-validator Altair registry, one slot before
    an epoch boundary."""
    from eth_consensus_specs_tpu.forks import get_spec
    from eth_consensus_specs_tpu.test_infra.genesis import create_genesis_state
    from eth_consensus_specs_tpu.utils import bls

    spec = get_spec("altair", "minimal")
    prev = bls.bls_active
    bls.bls_active = False
    try:
        state = create_genesis_state(
            spec, [spec.MAX_EFFECTIVE_BALANCE] * 64, spec.MAX_EFFECTIVE_BALANCE
        )
        spec.process_slots(state, 2 * int(spec.SLOTS_PER_EPOCH) - 1)
    finally:
        bls.bls_active = prev
    return spec, state


def test_full_incremental_and_forest_roots_agree_with_the_object_tree(altair_64):
    """The three programs that fold (full recompute, incremental epoch,
    root from a resident forest) on the same post-epoch state: one root,
    and it is the object tree's."""
    from eth_consensus_specs_tpu.ops import snapshot

    spec, state = altair_64
    state = state.copy()
    for i in range(0, len(state.validators), 3):
        state.balances[i] = int(state.balances[i]) - 12345
    cols, just, static = resident.ingest_full(spec, state)
    full = resident.run_epochs(spec, cols, just, 1, with_root="state", static=static)
    inc = resident.run_epochs(spec, cols, just, 1, with_root="state_inc", static=static)
    plan = resident.forest_plan_for(static)
    from_forest = snapshot.state_root_bytes(static, plan, inc.forest, inc.just)

    expected = state.copy()
    old_current = list(expected.current_epoch_participation)
    resident.writeback(spec, expected, full)
    part_t = type(expected.current_epoch_participation)
    expected.previous_epoch_participation = part_t(old_current)
    expected.current_epoch_participation = part_t([0] * len(old_current))
    object_root = bytes(ssz.hash_tree_root(expected))
    assert _root_bytes(full.root_acc) == object_root
    assert _root_bytes(inc.root_acc) == object_root
    assert from_forest == object_root


def test_chain_steps_counts_the_longest_chain_once(altair_64):
    """`state_root.chain_steps` is the sequential depth of a root's list
    tails: the longest chain's folds and ONE length mix, not that times
    the four lists that ride it."""
    from eth_consensus_specs_tpu import obs
    from eth_consensus_specs_tpu.ops import state_root as sr

    spec, state = altair_64
    cols, just, (arrays, meta) = resident.ingest_full(spec, state)
    assert sr.state_root_chain_steps(meta) == 35  # 40 - 6 folds and the mix
    assert sr.state_root_chain_steps(sr.synthetic_meta(spec, 2**20)) == 21

    def counters():
        c = obs.snapshot()["counters"]
        return c.get("state_root.roots", 0), c.get("state_root.chain_steps", 0)

    roots0, steps0 = counters()
    sr.post_epoch_state_root(
        arrays, meta, cols.balance, cols.effective_balance, cols.inactivity_scores, just
    )
    roots1, steps1 = counters()
    assert (roots1 - roots0, steps1 - steps0) == (1, 35)
