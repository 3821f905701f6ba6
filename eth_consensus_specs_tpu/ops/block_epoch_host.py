"""Numpy host oracle for ops/block_epoch.py — its fault.degrade fallback
(same contract as ops/state_root_host.py: no XLA in the replay,
native-SHA trees), and a third implementation corner for tests (object
path <-> device kernel <-> this oracle)."""

from __future__ import annotations

import numpy as np

from eth_consensus_specs_tpu.ops.block_epoch import BlockEpochParams


def _isqrt(v: int) -> int:
    import math

    return math.isqrt(int(v))


def base_reward_column_np(params: BlockEpochParams, eff: np.ndarray, total: int):
    per_inc = (
        params.effective_balance_increment * params.base_reward_factor
    ) // _isqrt(total)
    return (eff // np.uint64(params.effective_balance_increment)) * np.uint64(per_inc)


def sync_rewards_np(params: BlockEpochParams, total: int):
    per_inc = (
        params.effective_balance_increment * params.base_reward_factor
    ) // _isqrt(total)
    total_increments = total // params.effective_balance_increment
    total_base = per_inc * total_increments
    max_part = (
        total_base
        * params.sync_reward_weight
        // params.weight_denominator
        // params.slots_per_epoch
    )
    part = max_part // params.sync_committee_size
    prop = part * params.proposer_weight // (
        params.weight_denominator - params.proposer_weight
    )
    return int(part), int(prop)


def replay_block_epoch_np(
    params: BlockEpochParams,
    n: int,
    st0,
    blocks,
    eff: np.ndarray,
    withdrawable_epoch: np.ndarray,
    has_eth1_cred: np.ndarray,
    epoch: int,
    with_withdrawals: bool = True,
    root_fn=None,
):
    """Sequential numpy replay of block_epoch_chain.  `st0`/`blocks` are
    the same (numpy-converted) structures the kernel consumes.  With
    `root_fn(balance, cur_part, prev_part, slot_no) -> u32[8]` the
    per-slot root xor-chain is accumulated too.  Returns
    (balance, cur_part, prev_part, next_wd_index, next_wd_validator,
    root_acc)."""
    bal = np.array(np.asarray(st0.balance), np.uint64, copy=True)
    cur = np.array(np.asarray(st0.cur_part), np.uint8, copy=True)
    prev = np.array(np.asarray(st0.prev_part), np.uint8, copy=True)
    wd_index = int(np.asarray(st0.next_wd_index))
    wd_validator = int(np.asarray(st0.next_wd_validator))
    total = max(int(eff.sum()), params.effective_balance_increment)
    base_reward = base_reward_column_np(params, eff, total)
    part_r, prop_r = sync_rewards_np(params, total)
    denom = (
        (params.weight_denominator - params.proposer_weight)
        * params.weight_denominator
        // params.proposer_weight
    )
    acc = np.zeros(8, np.uint32)

    # one conversion per tensor — inside the loops these would re-copy
    # multi-MB arrays thousands of times
    b_att_idx = np.asarray(blocks.att_idx)
    b_att_bits = np.asarray(blocks.att_bits)
    b_att_flags = np.asarray(blocks.att_flags)
    b_att_is_current = np.asarray(blocks.att_is_current)
    b_att_pay = np.asarray(blocks.att_pay)
    b_proposer = np.asarray(blocks.proposer)
    b_sync_idx = np.asarray(blocks.sync_idx)
    b_sync_bits = np.asarray(blocks.sync_bits)
    b_dep_idx = np.asarray(blocks.dep_idx)
    b_dep_amt = np.asarray(blocks.dep_amt)

    S = b_proposer.shape[0]
    slot_no = epoch * params.slots_per_epoch + 1
    for s in range(S):
        # withdrawals sweep (forks/capella.py:223-281)
        if with_withdrawals:
            bound = min(n, params.max_validators_per_withdrawals_sweep)
            window = (wd_validator + np.arange(bound)) % n
            wbal = bal[window]
            full = (
                has_eth1_cred[window]
                & (withdrawable_epoch[window] <= np.uint64(epoch))
                & (wbal > 0)
            )
            partial = (
                has_eth1_cred[window]
                & (eff[window] == np.uint64(params.max_effective_balance))
                & (wbal > np.uint64(params.max_effective_balance))
            )
            elig = full | partial
            rank = np.cumsum(elig)
            take = elig & (rank <= params.max_withdrawals_per_payload)
            amount = np.where(full, wbal, wbal - np.uint64(params.max_effective_balance))
            bal[window[take]] = wbal[take] - amount[take]
            n_taken = int(min(rank[-1] if bound else 0, params.max_withdrawals_per_payload))
            if n_taken == params.max_withdrawals_per_payload:
                last_pos = int(np.max(np.nonzero(take)[0]))
                wd_validator = (wd_validator + last_pos + 1) % n
            else:
                wd_validator = (
                    wd_validator + params.max_validators_per_withdrawals_sweep
                ) % n
            wd_index += n_taken

        # attestations, in block order; the proposer numerator carries
        # across an aggregate's per-committee rows and divides once at
        # the pay boundary (electra EIP-7549 shape)
        A = b_att_idx.shape[1]
        proposer = int(b_proposer[s])
        carry_num = 0
        for a in range(A):
            idx = b_att_idx[s, a]
            bits = b_att_bits[s, a]
            flags = int(b_att_flags[s, a])
            pay = bool(b_att_pay[s, a])
            if flags != 0:
                live = (idx < n) & bits
                part = cur if bool(b_att_is_current[s, a]) else prev
                li = idx[live].astype(np.int64)
                pre = part[li]
                new_bits = np.uint8(flags) & ~pre
                part[li] = pre | new_bits
                weight_sum = np.zeros(li.shape[0], np.uint64)
                for b, w in enumerate(params.weights):
                    weight_sum += np.where((new_bits >> b) & 1, np.uint64(w), np.uint64(0))
                carry_num += int((weight_sum * base_reward[li]).sum())
            if pay:
                bal[proposer] += np.uint64(carry_num // denom)
                carry_num = 0

        # deposits (existing-key top-ups)
        didx = b_dep_idx[s]
        damt = b_dep_amt[s]
        for j in range(didx.shape[0]):
            if didx[j] < n:
                bal[int(didx[j])] += np.uint64(damt[j])

        # sync aggregate — spec order: one op per committee position
        sidx = b_sync_idx[s].astype(np.int64)
        sbits = b_sync_bits[s]
        for pos in range(sidx.shape[0]):
            i = int(sidx[pos])
            if sbits[pos]:
                bal[i] += np.uint64(part_r)
                bal[proposer] += np.uint64(prop_r)
            else:
                bal[i] = bal[i] - np.uint64(part_r) if bal[i] >= part_r else np.uint64(0)

        if root_fn is not None:
            acc = acc ^ root_fn(bal, cur, prev, slot_no)
        slot_no += 1

    return bal, cur, prev, wd_index, wd_validator, acc


def slot_root_fn_from_ctx(ctx):
    """Host slot-root fn straight from a device SlotRootCtx
    (block_epoch.make_root_ctx output): the per-epoch-constant top chunks
    are already filled on the ctx, so the host leg only re-reduces the
    dirty columns — this is fault.degrade's fallback, which must work
    from exactly the inputs the device path had."""
    from eth_consensus_specs_tpu.ops.state_root import (
        BALANCE_LIMIT_CHUNKS_LOG2,
        PARTICIPATION_LIMIT_CHUNKS_LOG2,
    )
    from eth_consensus_specs_tpu.ops.state_root_host import (
        tree_root_np,
        u8_list_root_np,
        u64_chunk_words_np,
        u64_list_root_np,
        zerohash_words,
    )

    n = ctx.n
    zh = zerohash_words(41)
    chunks = np.array(np.asarray(ctx.top_chunks), np.uint32, copy=True)

    def root_fn(bal, cur, prev, slot_no):
        c = chunks.copy()
        c[ctx.slot_field_index] = u64_chunk_words_np(int(slot_no))
        c[ctx.balances_slot] = u64_list_root_np(
            np.asarray(bal), n, BALANCE_LIMIT_CHUNKS_LOG2, zh
        )
        c[ctx.cur_part_slot] = u8_list_root_np(
            np.asarray(cur), n, PARTICIPATION_LIMIT_CHUNKS_LOG2, zh
        )
        c[ctx.prev_part_slot] = u8_list_root_np(
            np.asarray(prev), n, PARTICIPATION_LIMIT_CHUNKS_LOG2, zh
        )
        return tree_root_np(c, ctx.top_depth)

    return root_fn


def slot_root_fn_np(spec, arrays, meta, static, scores, just):
    """Host mirror of block_epoch.make_root_ctx + _slot_root: fill the
    per-epoch-constant top chunks once, then per-slot reduce only the
    dirty columns through the native-SHA trees."""
    from eth_consensus_specs_tpu.ops.state_root import (
        BALANCE_LIMIT_CHUNKS_LOG2,
        PARTICIPATION_LIMIT_CHUNKS_LOG2,
    )
    from eth_consensus_specs_tpu.ops.state_root_host import (
        bitvector4_chunk_np,
        checkpoint_root_np,
        tree_root_np,
        u8_list_root_np,
        u64_chunk_words_np,
        u64_list_root_np,
        validator_registry_root_np,
        zerohash_words,
    )

    n = meta.n_validators
    zh = zerohash_words(41)
    slot_of = {name: i for i, name in meta.dynamic_slots}
    chunks = np.array(np.asarray(arrays.top_chunks), np.uint32, copy=True)
    chunks[slot_of["validators"]] = validator_registry_root_np(
        np.asarray(arrays.val_node_a),
        np.asarray(arrays.val_node_f),
        np.asarray(arrays.slashed_chunk),
        np.asarray(static.eff_balance),
        zh,
    )
    if "inactivity_scores" in slot_of:
        chunks[slot_of["inactivity_scores"]] = u64_list_root_np(
            np.asarray(scores), n, BALANCE_LIMIT_CHUNKS_LOG2, zh
        )
    chunks[slot_of["justification_bits"]] = bitvector4_chunk_np(
        np.asarray(just.justification_bits).astype(bool)
    )
    chunks[slot_of["previous_justified_checkpoint"]] = checkpoint_root_np(
        int(just.prev_justified_epoch), np.asarray(just.prev_justified_root)
    )
    chunks[slot_of["current_justified_checkpoint"]] = checkpoint_root_np(
        int(just.cur_justified_epoch), np.asarray(just.cur_justified_root)
    )
    chunks[slot_of["finalized_checkpoint"]] = checkpoint_root_np(
        int(just.finalized_epoch), np.asarray(just.finalized_root)
    )
    fields = list(spec.BeaconState.fields())
    slot_field = fields.index("slot")

    def root_fn(bal, cur, prev, slot_no):
        c = chunks.copy()
        c[slot_field] = u64_chunk_words_np(int(slot_no))
        c[slot_of["balances"]] = u64_list_root_np(bal, n, BALANCE_LIMIT_CHUNKS_LOG2, zh)
        c[slot_of["current_epoch_participation"]] = u8_list_root_np(
            cur, n, PARTICIPATION_LIMIT_CHUNKS_LOG2, zh
        )
        c[slot_of["previous_epoch_participation"]] = u8_list_root_np(
            prev, n, PARTICIPATION_LIMIT_CHUNKS_LOG2, zh
        )
        return tree_root_np(c, meta.top_depth)

    return root_fn
