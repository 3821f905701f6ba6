"""Columnar per-slot BLOCK processing — the device path for a full epoch
of `state_transition` at registry scale (BASELINE config #4: 128
attestations/slot x 32 slots @ 1M validators, < 1 s).

What the reference does per block (and where):
  * process_attestation — committee bit-accumulation into participation
    flags + proposer-reward attribution
    (reference: specs/altair/beacon-chain.md:509-556 equivalent,
    specs/phase0/beacon-chain.md:1980-2006);
  * process_sync_aggregate — per-slot sync-committee rewards
    (specs/altair/beacon-chain.md:575-650);
  * process_deposit (existing-key top-up path,
    specs/phase0/beacon-chain.md:1852-1905);
  * get_expected_withdrawals / process_withdrawals — bounded circular
    sweep (specs/capella/beacon-chain.md:286-345).

TPU-first design: block bodies for a whole epoch are ingested ONCE into
fixed-shape index/bit/flag tensors (`BlockColumns`), then the epoch runs
as one jit — `lax.scan` over slots, an inner `lax.scan` over the slot's
attestations (the spec's "already set?" semantics make attestations
order-dependent WITHIN a block, so they form a scan, not a reduction;
every per-attestation step is itself fully vectorized over the committee
axis).  Gathers/scatters ride XLA's native dynamic-(update-)slice path;
no Python-level loop survives into the graph.

The per-slot dirty state root reuses ops/state_root.py subtrees: per
slot only balances + the two participation columns (+ the slot chunk)
move, so the validator-registry/scores/checkpoint subtree roots are
computed once per epoch and the slot root re-reduces just the dirty
columns and the ~32-chunk top combine.  Slot-cadence history vectors
(block_roots/state_roots/randao mixes/latest header) are modeled as
static top chunks — registry-scale hash work is the target here; their
13-hash incremental paths are noise at 1M validators.

Not modeled (rare-path, host/spec-level): proposer/attester slashings,
voluntary exits, BLS-to-execution changes, new-validator deposits
(registry growth changes array shapes — host ingest concern).  The
object path remains authoritative for those; tests/test_block_epoch.py
proves this kernel bit-exact against it for the dense plane.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import numpy as np

import eth_consensus_specs_tpu  # noqa: F401  (package import enables x64)
import jax
import jax.numpy as jnp
from jax import lax

from eth_consensus_specs_tpu import fault, obs

U64 = jnp.uint64


@dataclass(frozen=True)
class BlockEpochParams:
    """Static (trace-time) preset constants."""

    slots_per_epoch: int
    effective_balance_increment: int
    base_reward_factor: int
    weights: tuple  # PARTICIPATION_FLAG_WEIGHTS (source, target, head)
    weight_denominator: int
    proposer_weight: int
    sync_reward_weight: int
    sync_committee_size: int
    max_effective_balance: int
    max_withdrawals_per_payload: int
    max_validators_per_withdrawals_sweep: int

    @classmethod
    def from_spec(cls, spec) -> "BlockEpochParams":
        return cls(
            slots_per_epoch=int(spec.SLOTS_PER_EPOCH),
            effective_balance_increment=int(spec.EFFECTIVE_BALANCE_INCREMENT),
            base_reward_factor=int(spec.BASE_REWARD_FACTOR),
            weights=tuple(int(w) for w in spec.PARTICIPATION_FLAG_WEIGHTS),
            weight_denominator=int(spec.WEIGHT_DENOMINATOR),
            proposer_weight=int(spec.PROPOSER_WEIGHT),
            sync_reward_weight=int(spec.SYNC_REWARD_WEIGHT),
            sync_committee_size=int(spec.SYNC_COMMITTEE_SIZE),
            max_effective_balance=int(spec.MAX_EFFECTIVE_BALANCE),
            # pre-capella specs have no withdrawal sweep
            max_withdrawals_per_payload=int(
                getattr(spec, "MAX_WITHDRAWALS_PER_PAYLOAD", 0)
            ),
            max_validators_per_withdrawals_sweep=int(
                getattr(spec, "MAX_VALIDATORS_PER_WITHDRAWALS_SWEEP", 0)
            ),
        )


class BlockColumns(NamedTuple):
    """One epoch of block bodies as fixed-shape tensors.  PAD convention:
    validator-index lanes use index n (one past the registry) for absent
    entries; whole absent attestations/deposits have flags/amount 0."""

    att_idx: jnp.ndarray  # u32[S, A, C] committee member validator indices
    att_bits: jnp.ndarray  # bool[S, A, C] aggregation bits
    att_flags: jnp.ndarray  # u8[S, A] participation flag bits conferred
    att_is_current: jnp.ndarray  # bool[S, A] target epoch == current epoch
    # True on the LAST row of an on-chain attestation: electra aggregates
    # expand into one row per committee, and the spec divides the
    # proposer-reward numerator ONCE per attestation — rows accumulate
    # until the pay boundary (pre-electra: every row pays)
    att_pay: jnp.ndarray  # bool[S, A]
    proposer: jnp.ndarray  # u32[S]
    sync_idx: jnp.ndarray  # u32[S, SYNC] sync-committee validator indices
    sync_bits: jnp.ndarray  # bool[S, SYNC]
    dep_idx: jnp.ndarray  # u32[S, D] deposit target (existing validator)
    dep_amt: jnp.ndarray  # u64[S, D]


class BlockState(NamedTuple):
    """The dense mutable plane threaded through the slot scan."""

    balance: jnp.ndarray  # u64[N]
    cur_part: jnp.ndarray  # u8[N] current_epoch_participation
    prev_part: jnp.ndarray  # u8[N] previous_epoch_participation
    next_wd_index: jnp.ndarray  # u64 scalar
    next_wd_validator: jnp.ndarray  # u64 scalar


def base_reward_per_validator(params: BlockEpochParams, effective_balance, total_active):
    """get_base_reward as a column (specs/altair/beacon-chain.md:388-397):
    increments * (increment * factor // isqrt(total_active_balance))."""
    from eth_consensus_specs_tpu.ops.state_columns import isqrt_u64

    per_increment = (
        U64(params.effective_balance_increment) * U64(params.base_reward_factor)
    ) // isqrt_u64(total_active)
    return (effective_balance // U64(params.effective_balance_increment)) * per_increment


def sync_rewards(params: BlockEpochParams, total_active):
    """(participant_reward, proposer_reward) scalars for the epoch
    (specs/altair/beacon-chain.md:591-605)."""
    from eth_consensus_specs_tpu.ops.state_columns import isqrt_u64

    total_increments = total_active // U64(params.effective_balance_increment)
    per_increment = (
        U64(params.effective_balance_increment) * U64(params.base_reward_factor)
    ) // isqrt_u64(total_active)
    total_base_rewards = per_increment * total_increments
    max_participant_rewards = (
        total_base_rewards
        * U64(params.sync_reward_weight)
        // U64(params.weight_denominator)
        // U64(params.slots_per_epoch)
    )
    participant_reward = max_participant_rewards // U64(params.sync_committee_size)
    proposer_reward = (
        participant_reward
        * U64(params.proposer_weight)
        // U64(params.weight_denominator - params.proposer_weight)
    )
    return participant_reward, proposer_reward


def _apply_attestation(params, n, base_reward, part, balance, proposer, att, carry_num):
    """One attestation row against one participation column: set
    newly-earned flags for attesting committee members, accumulate the
    proposer-reward numerator, and pay (one floor division per on-chain
    attestation) at the row group's pay boundary.  Committee indices are
    unique within a row, so the scatter is write-once; pad lanes
    (idx == n) add zero."""
    idx, bits, flags, pay = att
    safe = jnp.minimum(idx, jnp.uint32(n - 1))
    live = (idx < jnp.uint32(n)) & bits & (flags != jnp.uint8(0))
    pre = part[safe]
    new_bits = jnp.where(live, flags & ~pre, jnp.uint8(0))
    # scatter-ADD, not set: pad lanes alias index n-1, and duplicate-index
    # scatter-set order is unspecified — adds commute, pad lanes add 0,
    # and new_bits is disjoint from pre so add == bitwise-or here
    part = part.at[safe].add(new_bits)
    weight_sum = jnp.zeros_like(new_bits, dtype=U64)
    for b, w in enumerate(params.weights):
        weight_sum = weight_sum + jnp.where(
            (new_bits >> b) & 1, U64(w), U64(0)
        )
    carry_num = carry_num + jnp.sum(weight_sum * base_reward[safe])
    denominator = U64(
        (params.weight_denominator - params.proposer_weight)
        * params.weight_denominator
        // params.proposer_weight
    )
    balance = balance.at[proposer].add(jnp.where(pay, carry_num // denominator, U64(0)))
    carry_num = jnp.where(pay, U64(0), carry_num)
    return part, balance, carry_num


def _apply_sync(params, st: BlockState, proposer, sync_idx, sync_bits, part_r, prop_r, n):
    """process_sync_aggregate balance plane in EXACT spec order: a scan
    over committee positions (increase participant + proposer per set
    bit, clamped decrease per unset bit).  Sync committees sample WITH
    replacement and decrease_balance clamps per OPERATION, so the
    position walk is genuinely sequential for a validator whose balance
    can cross zero mid-committee — a summed-then-clamped shortcut
    diverges there.  512 scan steps/slot is noise against the slot's
    tree work."""

    def step(bal, x):
        i, bit = x
        cur = bal[i]
        dec = jnp.where(cur >= part_r, cur - part_r, U64(0))
        bal = bal.at[i].set(jnp.where(bit, cur + part_r, dec))
        bal = bal.at[proposer].add(jnp.where(bit, prop_r, U64(0)))
        return bal, None

    bal, _ = lax.scan(step, st.balance, (sync_idx, sync_bits))
    return st._replace(balance=bal)


def _apply_deposits(st: BlockState, dep_idx, dep_amt, n):
    safe = jnp.minimum(dep_idx, jnp.uint32(n - 1))
    amt = jnp.where(dep_idx < jnp.uint32(n), dep_amt, U64(0))
    return st._replace(balance=st.balance.at[safe].add(amt))


def _apply_withdrawals(
    params, st: BlockState, epoch, eff_balance, withdrawable_epoch, has_eth1_cred, n
):
    """The capella sweep as one vectorized window: gather `bound`
    validators from the circular pointer, rank the eligible ones by
    cumulative count, pay the first MAX_WITHDRAWALS, advance pointers by
    the spec's two rules (forks/capella.py:223-281)."""
    bound = min(n, params.max_validators_per_withdrawals_sweep)
    max_w = params.max_withdrawals_per_payload
    start = st.next_wd_validator
    window = ((start + jnp.arange(bound, dtype=U64)) % U64(n)).astype(jnp.uint32)
    bal = st.balance[window]
    full = has_eth1_cred[window] & (withdrawable_epoch[window] <= epoch) & (bal > U64(0))
    partial = (
        has_eth1_cred[window]
        & (eff_balance[window] == U64(params.max_effective_balance))
        & (bal > U64(params.max_effective_balance))
    )
    elig = full | partial
    rank = jnp.cumsum(elig.astype(jnp.uint32))
    take = elig & (rank <= jnp.uint32(max_w))
    amount = jnp.where(full, bal, bal - U64(params.max_effective_balance))
    new_bal = st.balance.at[window].set(jnp.where(take, bal - amount, bal))
    n_taken = jnp.minimum(rank[-1], jnp.uint32(max_w)).astype(U64)
    # pointer advance: full payload resumes after the last paid validator,
    # otherwise the whole sweep window is skipped
    positions = jnp.arange(bound, dtype=jnp.uint32)
    last_pos = jnp.max(jnp.where(take, positions, jnp.uint32(0)))
    full_payload = n_taken == U64(max_w)
    next_validator = jnp.where(
        full_payload,
        (start + last_pos.astype(U64) + U64(1)) % U64(n),
        (start + U64(params.max_validators_per_withdrawals_sweep)) % U64(n),
    )
    return st._replace(
        balance=new_bal,
        next_wd_index=st.next_wd_index + n_taken,
        next_wd_validator=next_validator,
    )


def process_slot_columnar(
    params: BlockEpochParams,
    n: int,
    st: BlockState,
    slot_blk,
    base_reward,
    eff_balance,
    withdrawable_epoch,
    has_eth1_cred,
    epoch,
    part_reward,
    prop_reward,
    with_withdrawals: bool = True,
) -> BlockState:
    """One slot's block against the dense plane, in spec order:
    withdrawals -> (randao/eth1: no dense effect) -> operations
    (attestations, deposits) -> sync aggregate."""
    (att_idx, att_bits, att_flags, att_is_current, att_pay, proposer, sync_idx,
     sync_bits, dep_idx, dep_amt) = slot_blk
    if with_withdrawals:
        st = _apply_withdrawals(
            params, st, epoch, eff_balance, withdrawable_epoch, has_eth1_cred, n
        )

    def att_step(carry, att):
        cur, prev, bal, num = carry
        idx, bits, flags, is_cur, pay = att

        def on_cur(args):
            cur, prev, bal, num = args
            cur, bal, num = _apply_attestation(
                params, n, base_reward, cur, bal, proposer, (idx, bits, flags, pay), num
            )
            return cur, prev, bal, num

        def on_prev(args):
            cur, prev, bal, num = args
            prev, bal, num = _apply_attestation(
                params, n, base_reward, prev, bal, proposer, (idx, bits, flags, pay), num
            )
            return cur, prev, bal, num

        return lax.cond(is_cur, on_cur, on_prev, (cur, prev, bal, num)), None

    (cur, prev, bal, _num), _ = lax.scan(
        att_step,
        (st.cur_part, st.prev_part, st.balance, U64(0)),
        (att_idx, att_bits, att_flags, att_is_current, att_pay),
    )
    st = st._replace(cur_part=cur, prev_part=prev, balance=bal)
    st = _apply_deposits(st, dep_idx, dep_amt, n)
    st = _apply_sync(params, st, proposer, sync_idx, sync_bits, part_reward, prop_reward, n)
    return st


# ----------------------------------------------------------- epoch chain --


class BlockEpochStatic(NamedTuple):
    """Per-epoch constants the slot scan closes over."""

    base_reward: jnp.ndarray  # u64[N]
    eff_balance: jnp.ndarray  # u64[N]
    withdrawable_epoch: jnp.ndarray  # u64[N]
    has_eth1_cred: jnp.ndarray  # bool[N]
    epoch: jnp.ndarray  # u64
    part_reward: jnp.ndarray  # u64
    prop_reward: jnp.ndarray  # u64


def make_epoch_static(params, eff_balance, withdrawable_epoch, has_eth1_cred, epoch):
    active = eff_balance  # the synthetic world: all validators active
    total = jnp.maximum(
        jnp.sum(active), U64(params.effective_balance_increment)
    )
    part_r, prop_r = sync_rewards(params, total)
    return BlockEpochStatic(
        base_reward=base_reward_per_validator(params, eff_balance, total),
        eff_balance=eff_balance,
        withdrawable_epoch=withdrawable_epoch,
        has_eth1_cred=has_eth1_cred,
        epoch=jnp.asarray(epoch, U64),
        part_reward=part_r,
        prop_reward=prop_r,
    )


def block_epoch_chain(
    params: BlockEpochParams,
    n: int,
    st: BlockState,
    blocks: BlockColumns,
    static: BlockEpochStatic,
    root_ctx=None,
    with_withdrawals: bool = True,
):
    """Scan an epoch of blocks over the dense plane inside one jit.  With
    `root_ctx` (see `make_root_ctx`) each slot also recomputes the dirty
    state-root subtrees (balances + both participation columns + the slot
    chunk over the cached static tree) and xor-chains the root, so no
    slot's root can be skipped or reordered.  Returns (BlockState,
    root_acc u32[8])."""
    if obs.tracing(st.balance):
        obs.count("block_epoch.traces", 1)
        return _block_epoch_chain_impl(
            params, n, st, blocks, static, root_ctx, with_withdrawals
        )
    slots = params.slots_per_epoch
    work_bytes = slots * 2 * sum(
        int(getattr(a, "nbytes", 0)) for a in (st.balance, st.cur_part, st.prev_part)
    )
    if root_ctx is not None:
        from eth_consensus_specs_tpu.ops.state_root import slot_root_real_hashes

        work_bytes += slots * 96 * slot_root_real_hashes(n, root_ctx.top_depth)
    def _device():
        fault.check("block_epoch.device")
        with obs.span(
            "block_epoch.chain", work_bytes=work_bytes, n_validators=n, slots=slots
        ) as sp:
            out = _block_epoch_chain_impl(
                params, n, st, blocks, static, root_ctx, with_withdrawals
            )
            sp.result = out
        return out

    # device-side death (compile/OOM/injected) degrades to the numpy
    # replay + native-sha slot roots (ops/block_epoch_host.py)
    out = fault.degrade(
        "block_epoch.device",
        _device,
        lambda: _block_epoch_chain_host(
            params, n, st, blocks, static, root_ctx, with_withdrawals
        ),
    )
    obs.count("block_epoch.epochs", 1)
    obs.count("block_epoch.slots", slots)
    obs.count("block_epoch.validator_slots", n * slots)
    return out


def _block_epoch_chain_impl(
    params: BlockEpochParams,
    n: int,
    st: BlockState,
    blocks: BlockColumns,
    static: BlockEpochStatic,
    root_ctx,
    with_withdrawals: bool,
):
    def slot_step(carry, xs):
        st, acc, slot_no = carry
        st = process_slot_columnar(
            params,
            n,
            st,
            xs,
            static.base_reward,
            static.eff_balance,
            static.withdrawable_epoch,
            static.has_eth1_cred,
            static.epoch,
            static.part_reward,
            static.prop_reward,
            with_withdrawals=with_withdrawals,
        )
        if root_ctx is not None:
            root = _slot_root(root_ctx, st, slot_no)
            acc = acc ^ root
        return (st, acc, slot_no + U64(1)), None

    acc0 = jnp.zeros(8, jnp.uint32)
    slot0 = static.epoch * U64(params.slots_per_epoch) + U64(1)
    (st, acc, _), _ = lax.scan(slot_step, (st, acc0, slot0), blocks)
    return st, acc


def _block_epoch_chain_host(
    params: BlockEpochParams,
    n: int,
    st: BlockState,
    blocks: BlockColumns,
    static: BlockEpochStatic,
    root_ctx,
    with_withdrawals: bool,
):
    """fault.degrade fallback for block_epoch_chain: the sequential numpy
    replay + native-sha slot roots (ops/block_epoch_host.py) — the
    independent leg the tests compare the kernel against, repackaged
    into the kernel's (BlockState, root_acc) contract."""
    from eth_consensus_specs_tpu.ops.block_epoch_host import (
        replay_block_epoch_np,
        slot_root_fn_from_ctx,
    )

    root_fn = slot_root_fn_from_ctx(root_ctx) if root_ctx is not None else None
    with obs.span("block_epoch.chain_host", n_validators=n):
        bal, cur, prev, wd_index, wd_validator, acc = replay_block_epoch_np(
            params,
            n,
            st,
            blocks,
            np.asarray(static.eff_balance),
            np.asarray(static.withdrawable_epoch),
            np.asarray(static.has_eth1_cred),
            int(np.asarray(static.epoch)),
            with_withdrawals=with_withdrawals,
            root_fn=root_fn,
        )
    new_st = BlockState(
        balance=jnp.asarray(bal),
        cur_part=jnp.asarray(cur),
        prev_part=jnp.asarray(prev),
        next_wd_index=U64(wd_index),
        next_wd_validator=U64(wd_validator),
    )
    return new_st, jnp.asarray(acc)


# ------------------------------------------------------- per-slot rooting --


class SlotRootCtx(NamedTuple):
    """Static tree content for mid-epoch dirty roots: everything but
    balances/participation/slot reduced once per epoch."""

    top_chunks: jnp.ndarray  # u32[P, 8] with static + per-epoch roots filled
    zerohashes: jnp.ndarray
    top_depth: int
    n: int
    slot_field_index: int
    balances_slot: int
    cur_part_slot: int
    prev_part_slot: int


def make_root_ctx(spec, arrays, meta, static: BlockEpochStatic, scores, just) -> SlotRootCtx:
    """Fill every slow-moving top chunk once: validator registry root (eff
    balances are epoch-constant), inactivity scores, checkpoints — then
    per-slot work is just the three dirty columns + top combine."""
    from eth_consensus_specs_tpu.ops.state_root import (
        BALANCE_LIMIT_CHUNKS_LOG2,
        bitvector4_chunk,
        checkpoint_root,
        list_roots,
        u64_list_tail,
        validator_registry_tail,
    )

    n = meta.n_validators
    slot_of = {name: i for i, name in meta.dynamic_slots}
    chunks = arrays.top_chunks
    tails = {slot_of["validators"]: validator_registry_tail(arrays, n, static.eff_balance)}
    if "inactivity_scores" in slot_of:
        tails[slot_of["inactivity_scores"]] = u64_list_tail(
            scores, n, BALANCE_LIMIT_CHUNKS_LOG2
        )
    for slot, root in list_roots(tails, arrays.zerohashes).items():
        chunks = chunks.at[slot].set(root)
    chunks = chunks.at[slot_of["justification_bits"]].set(
        bitvector4_chunk(just.justification_bits.astype(bool))
    )
    chunks = chunks.at[slot_of["previous_justified_checkpoint"]].set(
        checkpoint_root(just.prev_justified_epoch, just.prev_justified_root)
    )
    chunks = chunks.at[slot_of["current_justified_checkpoint"]].set(
        checkpoint_root(just.cur_justified_epoch, just.cur_justified_root)
    )
    chunks = chunks.at[slot_of["finalized_checkpoint"]].set(
        checkpoint_root(just.finalized_epoch, just.finalized_root)
    )
    fields = list(spec.BeaconState.fields())
    return SlotRootCtx(
        top_chunks=chunks,
        zerohashes=arrays.zerohashes,
        top_depth=meta.top_depth,
        n=n,
        slot_field_index=fields.index("slot"),
        balances_slot=slot_of["balances"],
        cur_part_slot=slot_of["current_epoch_participation"],
        prev_part_slot=slot_of["previous_epoch_participation"],
    )


def _u64_chunk(v) -> jnp.ndarray:
    from eth_consensus_specs_tpu.ops.state_root import _u64_chunk_words

    return _u64_chunk_words(jnp.asarray(v, U64).reshape(1))[0]


def _slot_root(ctx: SlotRootCtx, st: BlockState, slot_no) -> jnp.ndarray:
    from eth_consensus_specs_tpu.ops.merkle import tree_root_words
    from eth_consensus_specs_tpu.ops.state_root import (
        BALANCE_LIMIT_CHUNKS_LOG2,
        PARTICIPATION_LIMIT_CHUNKS_LOG2,
        list_roots,
        u8_list_tail,
        u64_list_tail,
    )

    n = ctx.n
    chunks = ctx.top_chunks
    chunks = chunks.at[ctx.slot_field_index].set(_u64_chunk(slot_no))
    # the three dirty columns' tails as lanes of one fold
    tails = {
        ctx.balances_slot: u64_list_tail(st.balance, n, BALANCE_LIMIT_CHUNKS_LOG2),
        ctx.cur_part_slot: u8_list_tail(st.cur_part, n, PARTICIPATION_LIMIT_CHUNKS_LOG2),
        ctx.prev_part_slot: u8_list_tail(st.prev_part, n, PARTICIPATION_LIMIT_CHUNKS_LOG2),
    }
    for slot, root in list_roots(tails, ctx.zerohashes).items():
        chunks = chunks.at[slot].set(root)
    return tree_root_words(chunks, ctx.top_depth)


# ------------------------------------------------------------- ingest -----


def extract_block_columns(spec, pre_state, signed_blocks):
    """Harvest an epoch of object blocks into BlockColumns + the initial
    BlockState, replaying the object path for state-dependent context
    (committees, participation-flag indices, proposer/sync membership).
    Electra's committee-bit on-chain aggregates (EIP-7549) expand into
    one ROW per named committee, sharing a proposer-reward numerator up
    to the aggregate's pay boundary — beacon committees partition a
    slot's attesters, so the per-committee rows reproduce the spec's
    union exactly."""
    from eth_consensus_specs_tpu.config import is_post_fork

    post_electra = is_post_fork(spec.fork_name, "electra")
    state = pre_state.copy()
    n = len(state.validators)
    S = len(signed_blocks)
    obs.count("block_epoch.ingests", 1)
    obs.count("block_epoch.blocks_ingested", S)

    def _rows_of(state_now, att):
        """[(committee, bits_slice)] — one row per committee."""
        if not post_electra:
            committee = spec.get_beacon_committee(state_now, att.data.slot, att.data.index)
            return [(committee, [bool(b) for b in att.aggregation_bits])]
        rows = []
        offset = 0
        for ci in spec.get_committee_indices(att.committee_bits):
            committee = spec.get_beacon_committee(state_now, att.data.slot, ci)
            rows.append(
                (
                    committee,
                    [bool(att.aggregation_bits[offset + i]) for i in range(len(committee))],
                )
            )
            offset += len(committee)
        return rows or [([], [])]

    if post_electra:
        # the columnar plane models deneb-shaped deposit/withdrawal
        # semantics; electra's EIP-7251 queues change both — guard the
        # parts this ingest does NOT yet cover instead of mis-modeling
        # them silently (attestation semantics ARE fully covered)
        assert all(
            len(b.message.body.deposits) == 0 for b in signed_blocks
        ), "electra deposits route through pending_deposits — not columnar yet"
        assert len(getattr(pre_state, "pending_partial_withdrawals", [])) == 0, (
            "electra pending partial withdrawals not modeled in the sweep"
        )
        assert all(
            bytes(v.withdrawal_credentials)[:1] != b"\x02" for v in pre_state.validators
        ), "compounding (0x02) credentials not modeled in the sweep"

    SY = int(spec.SYNC_COMMITTEE_SIZE) if hasattr(spec, "SYNC_COMMITTEE_SIZE") else 0
    pk_to_index = {bytes(v.pubkey): i for i, v in enumerate(state.validators)}

    # ONE replay pass: buffer ragged per-slot rows, then pad into the
    # fixed-shape tensors (sizing needs no second pyspec replay)
    slot_rows: list[list[tuple]] = []  # (committee, bits, flags, is_cur, pay)
    slot_deps: list[list[tuple[int, int]]] = []
    proposer_l: list[int] = []
    sync_rows: list[tuple[list[int], list[bool]]] = []
    for signed in signed_blocks:
        block = signed.message
        if int(block.slot) > int(state.slot):
            spec.process_slots(state, int(block.slot))
        proposer_l.append(int(block.proposer_index))
        cur_epoch = spec.get_current_epoch(state)
        rows_here: list[tuple] = []
        for att in block.body.attestations:
            data = att.data
            flag_indices = spec.get_attestation_participation_flag_indices(
                state, data, int(state.slot) - int(data.slot)
            )
            flags = 0
            for fi in flag_indices:
                flags |= 1 << fi
            rows = _rows_of(state, att)
            is_cur = int(data.target.epoch) == int(cur_epoch)
            for r, (committee, bits) in enumerate(rows):
                rows_here.append(
                    ([int(v) for v in committee], bits, flags, is_cur, r == len(rows) - 1)
                )
        slot_rows.append(rows_here)
        if SY:
            agg = block.body.sync_aggregate
            sync_rows.append(
                (
                    [pk_to_index[bytes(pk)] for pk in state.current_sync_committee.pubkeys],
                    [bool(b) for b in agg.sync_committee_bits],
                )
            )
        deps_here = []
        for dep in block.body.deposits:
            idx = pk_to_index.get(bytes(dep.data.pubkey))
            assert idx is not None, "columnar ingest covers existing-key deposits"
            deps_here.append((idx, int(dep.data.amount)))
        slot_deps.append(deps_here)
        spec.process_block(state, block)

    A = max((len(rows) for rows in slot_rows), default=1) or 1
    C = max(
        (len(cm) for rows in slot_rows for cm, *_ in rows), default=1
    ) or 1
    D = max((len(d) for d in slot_deps), default=0) or 1

    att_idx = np.full((S, A, C), n, np.uint32)
    att_bits = np.zeros((S, A, C), bool)
    att_flags = np.zeros((S, A), np.uint8)
    att_is_current = np.zeros((S, A), bool)
    att_pay = np.ones((S, A), bool)
    proposer = np.asarray(proposer_l, np.uint32)
    sync_idx = np.zeros((S, max(SY, 1)), np.uint32)
    sync_bits = np.zeros((S, max(SY, 1)), bool)
    dep_idx = np.full((S, D), n, np.uint32)
    dep_amt = np.zeros((S, D), np.uint64)
    for s in range(S):
        for a, (committee, bits, flags, is_cur, pay) in enumerate(slot_rows[s]):
            att_flags[s, a] = flags
            att_is_current[s, a] = is_cur
            att_pay[s, a] = pay
            if committee:
                att_idx[s, a, : len(committee)] = committee
                att_bits[s, a, : len(bits)] = bits
        if SY:
            sync_idx[s] = sync_rows[s][0]
            sync_bits[s] = sync_rows[s][1]
        for d, (idx, amt) in enumerate(slot_deps[s]):
            dep_idx[s, d] = idx
            dep_amt[s, d] = amt

    cols = BlockColumns(
        att_idx=jnp.asarray(att_idx),
        att_bits=jnp.asarray(att_bits),
        att_flags=jnp.asarray(att_flags),
        att_is_current=jnp.asarray(att_is_current),
        att_pay=jnp.asarray(att_pay),
        proposer=jnp.asarray(proposer),
        sync_idx=jnp.asarray(sync_idx),
        sync_bits=jnp.asarray(sync_bits),
        dep_idx=jnp.asarray(dep_idx),
        dep_amt=jnp.asarray(dep_amt),
    )
    st0 = BlockState(
        balance=jnp.asarray(np.array([int(b) for b in pre_state.balances], np.uint64)),
        cur_part=jnp.asarray(
            np.array([int(f) for f in pre_state.current_epoch_participation], np.uint8)
        ),
        prev_part=jnp.asarray(
            np.array([int(f) for f in pre_state.previous_epoch_participation], np.uint8)
        ),
        next_wd_index=U64(int(getattr(pre_state, "next_withdrawal_index", 0))),
        next_wd_validator=U64(
            int(getattr(pre_state, "next_withdrawal_validator_index", 0))
        ),
    )
    return cols, st0


def synthetic_block_columns(
    spec, n: int, seed: int = 0, atts_per_slot: int = 128, committee_cap: int | None = None
) -> tuple[BlockColumns, BlockState, BlockEpochStatic]:
    """Bench-scale inputs without an object state: every slot carries
    `atts_per_slot` full attestations over disjoint committees (the
    mainnet shape: 64 committees x 2 slots of lookback coverage), a full
    sync aggregate, a few deposits.  Deterministic in `seed`."""
    params = BlockEpochParams.from_spec(spec)
    S = params.slots_per_epoch
    rng = np.random.default_rng(seed)
    if committee_cap is None:
        committee_cap = max(8, int(np.ceil(n / (S * max(atts_per_slot // 2, 1)))))
        committee_cap = 1 << (committee_cap - 1).bit_length()
    A, C = atts_per_slot, committee_cap

    att_idx = np.full((S, A, C), n, np.uint32)
    att_bits = np.zeros((S, A, C), bool)
    for s in range(S):
        perm = rng.permutation(n).astype(np.uint32)
        rows = max(min(A, n // C), 1)
        flat = perm[: rows * C]
        committees = np.full((rows, C), n, np.uint32)
        committees.ravel()[: flat.shape[0]] = flat
        reps = -(-A // rows)  # re-vote committees until A attestations exist
        att_idx[s] = np.tile(committees, (reps, 1))[:A]
        att_bits[s] = rng.random((A, C)) < 0.9
    att_flags = np.full((S, A), 0b111, np.uint8)
    att_is_current = rng.random((S, A)) < 0.7
    # ~1/4 of rows continue into the next row's aggregate (the electra
    # multi-committee shape), exercising the carried numerator; rows of
    # one aggregate share their attestation data's target epoch
    att_pay = rng.random((S, A)) < 0.75
    att_pay[:, -1] = True
    for a in range(1, A):
        cont = ~att_pay[:, a - 1]
        att_is_current[cont, a] = att_is_current[cont, a - 1]

    SY = params.sync_committee_size
    cols = BlockColumns(
        att_idx=jnp.asarray(att_idx),
        att_bits=jnp.asarray(att_bits),
        att_flags=jnp.asarray(att_flags),
        att_is_current=jnp.asarray(att_is_current),
        att_pay=jnp.asarray(att_pay),
        proposer=jnp.asarray(rng.integers(0, n, S, dtype=np.int64).astype(np.uint32)),
        sync_idx=jnp.asarray(rng.integers(0, n, (S, SY), dtype=np.int64).astype(np.uint32)),
        sync_bits=jnp.asarray(rng.random((S, SY)) < 0.95),
        dep_idx=jnp.asarray(rng.integers(0, n, (S, 16), dtype=np.int64).astype(np.uint32)),
        dep_amt=jnp.asarray(
            rng.integers(1, 32_000_000_000, (S, 16), dtype=np.int64).astype(np.uint64)
        ),
    )
    balance = rng.integers(31_000_000_000, 33_000_000_000, n, dtype=np.int64).astype(
        np.uint64
    )
    # a stripe of near-zero balances so the sync penalty's per-operation
    # clamp (and its order sensitivity under duplicates) is exercised
    balance[:: max(n // 17, 1)] = rng.integers(
        0, 3, balance[:: max(n // 17, 1)].shape[0], dtype=np.int64
    ).astype(np.uint64)
    st0 = BlockState(
        balance=jnp.asarray(balance),
        cur_part=jnp.asarray(np.zeros(n, np.uint8)),
        prev_part=jnp.asarray(
            rng.integers(0, 8, n, dtype=np.int64).astype(np.uint8)
        ),
        next_wd_index=U64(0),
        next_wd_validator=U64(0),
    )
    eff = (balance // 1_000_000_000 * 1_000_000_000).astype(np.uint64)
    eff = np.minimum(eff, np.uint64(params.max_effective_balance))
    wd_epoch = np.full(n, 2**64 - 1, np.uint64)
    wd_epoch[rng.random(n) < 0.001] = 1  # a few fully-withdrawable
    static = make_epoch_static(
        params,
        jnp.asarray(eff),
        jnp.asarray(wd_epoch),
        jnp.asarray(np.ones(n, bool)),
        10,
    )
    return cols, st0, static
