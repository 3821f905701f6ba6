"""The whole-slot state-transition pipeline — five benches as ONE op.

The paper's headline scenario (SNIPPETS.md header; ROADMAP item 3) is
``state_transition`` over a full registry served as ONE request, yet
every ingredient landed in isolation: resident state + incremental
root (PR 11/16), committee aggregation (PR 13), batched BLS + KZG
verification (PRs 8/14). This module composes them. One
:class:`SlotRequest` carries a block's attestations (ragged
committees), its sync aggregate, and its blob sidecars; the device
pipeline chains

  * **slot.verify** — every attestation's claimed aggregate signature,
    the sync aggregate, and every blob's KZG proof through the batched
    RLC paths (``ops/bls_batch.verify_many`` — ONE pairing for an
    all-valid slot, bisection isolates the invalid — and
    ``ops/kzg_batch.verify_many_blobs``);
  * **slot.aggregate** — the VALID attestations' signatures folded into
    per-subnet aggregates through the ONE batched G2 many-sum
    (``ops/g2_aggregate.sum_g2_many_device``, the PR 13 kernel, same
    live ``g2_agg`` compile key);
  * **column update + slot.reroot** — the participation/balance
    scatter (the 14th kernel family, ``slot_apply``) fused with the
    incremental state re-root against the RESIDENT forest
    (``ops/state_root.post_epoch_state_root_inc`` — the forest is
    DONATED in place, the PR 11/16 lifecycle); an epoch-boundary slot
    additionally runs one accounting epoch through
    ``parallel/resident.run_epochs(with_root="state_inc")``.

Every leg is bit-identical to the sequential host fold of the same
ops (:func:`host_slot_fold`) — the parity gate every tier and the
slot-machine bench (scripts/slot_bench.py) REFUSE to violate.

Semantics (honest about what the resident world models): a VALID
attestation sets its participating members' previous-epoch
participation flags (source|target|head) and the TIMELY_TARGET column
the epoch accounting reads; a VALID sync aggregate credits each sync
participant a fixed ``ETH_SPECS_SLOT_SYNC_REWARD`` gwei (the per-slot
balance mutation — process_sync_aggregate's shape). The state root
follows the resident convention (parallel/resident.py): balances /
effective balances / inactivity scores re-root incrementally; the
participation LIST root in the forest is the static stand-in, so flag
writes update the accounting columns but not the root — the same
documented caveat the resident loop carries.

Invalid inputs degrade the ITEM, never the slot: a bad attestation is
a ``False`` verdict excluded from aggregation and participation; a bad
blob is a ``False`` verdict; the rest of the slot lands normally.

Fault sites (fault/sites.py): ``slot.verify`` fires before any state
read, ``slot.reroot`` before the donating dispatch — both BEFORE any
mutation of the committed carry, so the degrade ladder (serve/slot.py)
re-runs the WHOLE slot as the host fold from the pre-slot columns and
commits all-or-nothing; a half-applied slot is unrepresentable.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from functools import lru_cache, partial

import numpy as np

from eth_consensus_specs_tpu import obs

# altair participation bits: TIMELY_SOURCE | TIMELY_TARGET | TIMELY_HEAD
FLAG_MASK = 0b111

# The (fork, preset) every slot world is built under — the serving owner,
# the resident owner, the bench and the tests all read it from here. The
# preset fixes the epoch quotients and the shapes of the state's static
# subtrees, so a registry of deployment size under another preset is not
# a state any user holds.
SLOT_SPEC = ("altair", "mainnet")


def slot_spec():
    from eth_consensus_specs_tpu.forks import get_spec

    return get_spec(*SLOT_SPEC)


def sync_reward_gwei() -> int:
    """Per-participant balance credit of a valid sync aggregate (the
    slot-level balance mutation; deterministic, env-snapshotted)."""
    raw = os.environ.get("ETH_SPECS_SLOT_SYNC_REWARD", "")
    try:
        return max(int(raw), 0) if raw else 1024
    except ValueError:
        return 1024


# ------------------------------------------------------------ wire types --


@dataclass(frozen=True)
class SlotAttestation:
    """One aggregated attestation as a block carries it: the claimed
    aggregate signature over the participating committee members."""

    subnet: int
    root: bytes  # attestation data root — the signed message
    committee: tuple  # validator indices of the FULL committee
    bits: tuple  # participation bits over the full committee
    pubkeys: tuple  # participating members' compressed pubkeys (48B)
    sig: bytes  # claimed aggregate signature (96B)


@dataclass(frozen=True)
class SlotRequest:
    """Everything one block submits: attestations, sync aggregate, blob
    sidecars. ``slot`` is the idempotency key — a retried slot that
    already committed replays its recorded verdicts instead of
    double-applying. ``epoch_boundary`` marks the slot that closes an
    epoch (one resident accounting epoch runs after the column
    updates). Plain data end to end — pickles across the replica wire
    unchanged."""

    slot: int
    attestations: tuple = ()
    sync_pubkeys: tuple = ()  # compressed pubkeys of sync participants
    sync_message: bytes = b""
    sync_sig: bytes = b""
    sync_indices: tuple = ()  # validator indices credited when valid
    blobs: tuple = ()  # (blob, commitment, proof) byte triples
    epoch_boundary: bool = False


@dataclass(frozen=True)
class SlotResult:
    """What ``submit_slot`` resolves to: the verdicts, the aggregation
    leg's per-subnet aggregates, and the canonical post-slot state root
    — every field bit-comparable against the sequential host fold."""

    slot: int
    att_verdicts: tuple  # bool per attestation
    sync_verdict: bool
    blob_verdicts: tuple  # bool per blob sidecar
    subnet_aggregates: tuple  # ((subnet, 96B aggregate sig) ...) valid atts
    state_root: bytes  # canonical combined root AFTER this slot
    epoch: int  # accounting epoch after this slot
    replayed: bool = False  # True: idempotent replay of a committed slot


@dataclass
class SlotPrep:
    """Host prep of one slot request (service ``_prep`` — overlapped
    with the previous flush's device work): decompressed signature
    points for the aggregation leg and parsed blob items for the KZG
    leg. Pure host work, no device touch."""

    sig_points: tuple = ()  # G2 Point | None per attestation
    blob_parsed: tuple = ()  # kzg_batch.parse_item output per blob


def prep_request(req: SlotRequest) -> SlotPrep:
    """Decompress/parse everything the device legs will need — the
    per-slot fixed host cost, paid off the dispatch thread."""
    from eth_consensus_specs_tpu.crypto.signature import _load_pk, _load_sig
    from eth_consensus_specs_tpu.ops.kzg_batch import parse_item

    for att in req.attestations:
        for pk in att.pubkeys:
            _load_pk(pk)  # warms the bounded decompression cache
    for pk in req.sync_pubkeys:
        _load_pk(pk)
    sig_points = tuple(_load_sig(att.sig) for att in req.attestations)
    blob_parsed = tuple(parse_item(b) for b in req.blobs)
    return SlotPrep(sig_points=sig_points, blob_parsed=blob_parsed)


# -------------------------------------------------------- update planning --


def request_capacity(req: SlotRequest) -> tuple[int, int]:
    """(flag capacity, reward capacity) of a request BEFORE any verdict
    exists: every set committee bit and every sync index, valid or not.
    The compile key buckets THIS — a shape derivable from the request
    alone, so the front door's router and the dispatch can never
    disagree — and invalid items simply leave no-op pad lanes."""
    flags = sum(1 for att in req.attestations for bit in att.bits if bit)
    return flags, len(req.sync_indices)


def plan_updates(
    req: SlotRequest, att_verdicts: list, sync_verdict: bool, n_validators: int
):
    """The deterministic scatter plan both legs share: which validators
    get participation flags and which get balance credits, from the
    VALID items only. Returns (flag_idx i32[], reward_idx i32[],
    reward_amt u64[]) — unpadded; the dispatch pads to the bucketed
    kernel shape. Out-of-range indices are dropped (a malformed request
    must not scatter outside the registry)."""
    flag_idx: list[int] = []
    for att, ok in zip(req.attestations, att_verdicts):
        if not ok:
            continue
        for vi, bit in zip(att.committee, att.bits):
            if bit and 0 <= int(vi) < n_validators:
                flag_idx.append(int(vi))
    reward_idx: list[int] = []
    if sync_verdict:
        reward = sync_reward_gwei()
        for vi in req.sync_indices:
            if 0 <= int(vi) < n_validators and reward > 0:
                reward_idx.append(int(vi))
    return (
        np.asarray(flag_idx, np.int32),
        np.asarray(reward_idx, np.int32),
        np.full(len(reward_idx), sync_reward_gwei(), np.uint64),
    )


# ------------------------------------------------------- the fused kernel --


@lru_cache(maxsize=None)
def _compiled_slot_apply(meta, plan, mesh, p_flags: int, p_rewards: int):
    """One executable per (registry shape, forest plan, mesh, padded
    update counts) — the 14th kernel family. The forest is DONATED:
    the slot chain updates the resident tree levels in place, exactly
    the run_epochs lifecycle (jaxlint's donation-audit proves the
    alias on the registered entry)."""
    import jax
    import jax.numpy as jnp

    from eth_consensus_specs_tpu.ops.state_root import post_epoch_state_root_inc

    @partial(jax.jit, donate_argnums=(1,))
    def run(
        arrays,
        forest,
        balance,
        effective_balance,
        inactivity_scores,
        prev_flags,
        cur_tgt_att,
        just,
        flag_idx,
        flag_on,
        reward_idx,
        reward_amt,
    ):
        # duplicate-safe participation scatter: a dense hit COUNT via
        # scatter-add (pad lanes carry index 0 with flag_on=0 — no-op;
        # u32 lane bounds the count at the padded lane count), then OR
        # the flag mask where hit > 0 — a validator two committees both
        # include gets the same flags either way.
        hit = (
            jnp.zeros(balance.shape[0], jnp.uint32)
            .at[flag_idx]
            .add(flag_on.astype(jnp.uint32))
        )
        new_flags = jnp.where(hit > 0, prev_flags | jnp.uint8(FLAG_MASK), prev_flags)
        new_tgt = cur_tgt_att | (hit > 0)
        # sync rewards legitimately accumulate across duplicate lanes
        # (pad lanes add 0 at index 0)
        new_balance = balance.at[reward_idx].add(reward_amt)
        forest, root = post_epoch_state_root_inc(
            arrays,
            meta,
            plan,
            forest,
            balance,
            effective_balance,
            inactivity_scores,
            new_balance,
            effective_balance,
            inactivity_scores,
            just,
            mesh=mesh,
        )
        return new_balance, new_flags, new_tgt, forest, root

    return run


def slot_apply_device(
    static,
    plan,
    forest,
    cols,
    just,
    flag_idx,
    reward_idx,
    reward_amt,
    mesh=None,
    cap_flags: int | None = None,
    cap_rewards: int | None = None,
):
    """Apply one slot's scatter plan and incrementally re-root: ONE
    donated dispatch. Returns (new_cols, new_forest, root_bytes).
    Compile-keyed by the LIVE ``serve/buckets.slot_key`` fn — pad
    shapes come from the key, so the dispatch and the analyzer always
    agree on the recompile surface. ``cap_flags``/``cap_rewards`` are
    the request-derived capacities (:func:`request_capacity`): bucketing
    the capacity instead of the post-verdict count keeps the key a pure
    function of the request shape."""
    import jax
    import jax.numpy as jnp

    from eth_consensus_specs_tpu.serve import buckets

    arrays, meta = static
    n = int(cols.balance.shape[0])
    key = buckets.slot_key(
        n,
        max(cap_flags if cap_flags is not None else len(flag_idx), len(flag_idx)),
        max(
            cap_rewards if cap_rewards is not None else len(reward_idx),
            len(reward_idx),
        ),
        plan,
        mesh=mesh,
    )
    p_flags, p_rewards = key[2], key[3]
    f_idx = np.zeros(p_flags, np.int32)
    f_on = np.zeros(p_flags, np.uint8)
    f_idx[: len(flag_idx)] = flag_idx
    f_on[: len(flag_idx)] = 1
    r_idx = np.zeros(p_rewards, np.int32)
    r_amt = np.zeros(p_rewards, np.uint64)
    r_idx[: len(reward_idx)] = reward_idx
    r_amt[: len(reward_amt)] = reward_amt
    run = _compiled_slot_apply(meta, plan, mesh, p_flags, p_rewards)
    with buckets.first_dispatch(*key):
        new_balance, new_flags, new_tgt, forest, root = run(
            jax.device_put(arrays),
            forest,
            cols.balance,
            cols.effective_balance,
            cols.inactivity_scores,
            cols.prev_flags,
            cols.cur_tgt_att,
            just,
            jnp.asarray(f_idx),
            jnp.asarray(f_on),
            jnp.asarray(r_idx),
            jnp.asarray(r_amt),
        )
    new_cols = cols._replace(
        balance=new_balance, prev_flags=new_flags, cur_tgt_att=new_tgt
    )
    return new_cols, forest, _root_bytes(root)


def _root_bytes(words) -> bytes:
    """u32[8] root words -> the canonical 32 big-endian bytes (the same
    encoding ops/snapshot.state_root_bytes commits to manifests)."""
    return np.asarray(words, np.uint32).astype(">u4").tobytes()


# -------------------------------------------------------- host twin legs --


def host_verify(req: SlotRequest) -> tuple[list, bool, list]:
    """The verify leg's host oracle: per-item
    ``crypto.signature.fast_aggregate_verify`` +
    ``ops.kzg_batch.verify_blob_host`` — exactly what the batched
    device paths are test-pinned against."""
    from eth_consensus_specs_tpu.crypto.signature import fast_aggregate_verify
    from eth_consensus_specs_tpu.ops.kzg_batch import verify_blob_host

    att = [
        bool(fast_aggregate_verify(list(a.pubkeys), a.root, a.sig))
        for a in req.attestations
    ]
    sync = bool(req.sync_pubkeys) and bool(
        fast_aggregate_verify(list(req.sync_pubkeys), req.sync_message, req.sync_sig)
    )
    blobs = [bool(verify_blob_host(*b)) for b in req.blobs]
    return att, sync, blobs


def device_verify(req: SlotRequest, prep: SlotPrep | None, mesh=None):
    """The verify leg on device: ONE RLC-batched BLS pass over every
    attestation + the sync aggregate (bisection isolates invalid
    items) and ONE batched KZG pass over the blob sidecars."""
    from eth_consensus_specs_tpu.ops.bls_batch import verify_many
    from eth_consensus_specs_tpu.ops.kzg_batch import verify_many_blobs

    items = [(list(a.pubkeys), a.root, a.sig) for a in req.attestations]
    n_att = len(items)
    if req.sync_pubkeys:
        items.append((list(req.sync_pubkeys), req.sync_message, req.sync_sig))
    verdicts = verify_many(items, mesh=mesh) if items else []
    att = [bool(v) for v in verdicts[:n_att]]
    sync = bool(verdicts[n_att]) if req.sync_pubkeys else False
    blobs = []
    if req.blobs:
        parsed = list(prep.blob_parsed) if prep is not None else None
        blobs = [
            bool(v)
            for v in verify_many_blobs(list(req.blobs), mesh=mesh, parsed=parsed)
        ]
    return att, sync, blobs


def _valid_by_subnet(req: SlotRequest, att_verdicts) -> list[tuple[int, list[int]]]:
    """(subnet, [attestation index...]) groups of the VALID attestations,
    subnet-sorted — the deterministic aggregation order both legs share."""
    groups: dict[int, list[int]] = {}
    for i, (att, ok) in enumerate(zip(req.attestations, att_verdicts)):
        if ok:
            groups.setdefault(int(att.subnet), []).append(i)
    return sorted(groups.items())


def host_aggregate(req: SlotRequest, att_verdicts) -> tuple:
    """The aggregation leg's host oracle: the ``crypto/signature``
    fold of each subnet's valid aggregate signatures."""
    from eth_consensus_specs_tpu.crypto.signature import aggregate

    out = []
    for subnet, idxs in _valid_by_subnet(req, att_verdicts):
        out.append((subnet, aggregate([req.attestations[i].sig for i in idxs])))
    return tuple(out)


def device_aggregate(
    req: SlotRequest, att_verdicts, prep: SlotPrep | None, mesh=None
) -> tuple:
    """The aggregation leg on device: every subnet's valid signatures
    in ONE batched G2 many-sum dispatch (the PR 13 kernel, the same
    LIVE ``g2_agg`` compile key the serve tier buckets by). The key
    buckets the REQUEST's subnets and attestations a subnet, valid or
    not (the :func:`request_capacity` rule): a refused attestation
    leaves an infinity lane, never a narrower compile."""
    from eth_consensus_specs_tpu.crypto.curve import g2_to_bytes
    from eth_consensus_specs_tpu.crypto.signature import _load_sig
    from eth_consensus_specs_tpu.ops.g2_aggregate import sum_g2_many_device
    from eth_consensus_specs_tpu.serve import buckets

    groups = _valid_by_subnet(req, att_verdicts)
    if not groups:
        return ()
    pts = list(prep.sig_points) if prep is not None else None
    lists = []
    for _, idxs in groups:
        row = []
        for i in idxs:
            p = pts[i] if pts is not None else _load_sig(req.attestations[i].sig)
            if p is None:  # unreachable for a True verdict; belt and braces
                p = _load_sig(req.attestations[i].sig)
            row.append(p)
        lists.append(row)
    per_subnet: dict[int, int] = {}
    for att in req.attestations:
        per_subnet[int(att.subnet)] = per_subnet.get(int(att.subnet), 0) + 1
    cap_items, cap_lanes = len(per_subnet), max(per_subnet.values())
    sharded = mesh is not None and buckets.route_wide(
        "agg", buckets.pow2_bucket(cap_lanes), cap_items
    )
    key = buckets.g2_agg_key(cap_items, cap_lanes, mesh=mesh if sharded else None)
    with buckets.first_dispatch(*key):
        sums = sum_g2_many_device(
            lists, mesh=mesh if sharded else None, pad_shape=(key[1], key[2])
        )
    return tuple(
        (subnet, g2_to_bytes(p)) for (subnet, _), p in zip(groups, sums)
    )


def advance_epoch(spec, cols, just):
    """One accounting epoch, the resident convention: the altair fused
    kernel advances balances/scores/justification, the epoch counter
    increments — the exact ``_advance`` body
    ``parallel/resident.run_epochs`` chains (integer arithmetic:
    eager and jitted execution are bit-identical)."""
    import jax.numpy as jnp

    from eth_consensus_specs_tpu.ops.altair_epoch import (
        AltairEpochParams,
        altair_epoch_accounting_impl,
    )

    params = AltairEpochParams.from_spec(spec)
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


def host_slot_fold(spec, static, cols, just, req: SlotRequest, epoch: int):
    """The WHOLE slot as a sequential host fold of the existing ops —
    the parity oracle every tier gates against and the degrade ladder's
    fallback. Verdicts via the per-item host oracles, aggregation via
    the ``crypto/signature`` fold, column updates as plain numpy
    scatters, the post-slot root via the full (non-incremental) host
    state-root oracle. Returns (SlotResult, new_cols, new_just) — the
    caller commits all-or-nothing."""
    import jax
    import jax.numpy as jnp

    from eth_consensus_specs_tpu.ops.state_root import post_epoch_state_root_host

    arrays, meta = static
    att_v, sync_v, blob_v = host_verify(req)
    subnet_aggs = host_aggregate(req, att_v)

    host = jax.tree_util.tree_map(np.asarray, cols)
    n = int(host.balance.shape[0])
    flag_idx, reward_idx, reward_amt = plan_updates(req, att_v, sync_v, n)
    new_flags = host.prev_flags.copy()
    new_flags[flag_idx] |= FLAG_MASK
    new_tgt = host.cur_tgt_att.copy()
    new_tgt[flag_idx] = True
    new_balance = host.balance.copy()
    np.add.at(new_balance, reward_idx, reward_amt)
    new_cols = cols._replace(
        balance=jnp.asarray(new_balance),
        prev_flags=jnp.asarray(new_flags),
        cur_tgt_att=jnp.asarray(new_tgt),
    )
    new_just = just
    new_epoch = int(epoch)
    if req.epoch_boundary:
        new_cols, new_just = advance_epoch(spec, new_cols, new_just)
        new_epoch += 1
    root = _root_bytes(
        np.asarray(
            post_epoch_state_root_host(
                arrays,
                meta,
                np.asarray(new_cols.balance),
                np.asarray(new_cols.effective_balance),
                np.asarray(new_cols.inactivity_scores),
                jax.tree_util.tree_map(np.asarray, new_just),
            )
        )
    )
    result = SlotResult(
        slot=int(req.slot),
        att_verdicts=tuple(att_v),
        sync_verdict=bool(sync_v),
        blob_verdicts=tuple(blob_v),
        subnet_aggregates=subnet_aggs,
        state_root=root,
        epoch=new_epoch,
    )
    obs.count("slot.host_folds", 1)
    return result, new_cols, new_just


# --------------------------------------------------------------- metrics --


def count_slot(req: SlotRequest) -> None:
    obs.count("slot.slots", 1)
    obs.count("slot.attestations", len(req.attestations))
    obs.count("slot.blobs", len(req.blobs))
