"""Columnar phase0 epoch accounting — ONE fused XLA computation.

The reference computes epoch rewards with Python loops over the validator
registry (reference: specs/phase0/beacon-chain.md:1466-1846 — five delta
components, each an O(validators) pass, plus slashings and the
effective-balance hysteresis sweep).  Here the whole accounting epoch is a
single jitted function over a *columnar* state: one uint64/bool array per
validator field, participation pre-reduced to per-component bit masks.  All
control flow is `jnp.where` on masks; there is no data-dependent branching,
so XLA fuses the entire epoch into a few elementwise kernels + reductions +
one scatter-add (proposer micro-rewards).

Fusion boundary (proved safe, see forks/phase0.py:process_epoch ordering):
the kernel runs justification/finalization -> rewards&penalties ->
slashings -> effective-balance updates.  `process_registry_updates` sits
between rewards and slashings in the spec, but it only mutates epochs of
*unslashed* validators to values in the future (> current_epoch + lookahead),
none of which feed the slashing predicate (requires `slashed`), the active
set at current_epoch, or the balance columns — so hoisting it out of the
fused region is bit-exact.  The host wrapper
(forks/phase0.py:process_epoch_columnar) runs it after the kernel.

All arithmetic is uint64 with floor division, matching the spec's
overflow-as-invalid integer semantics (reference:
specs/phase0/beacon-chain.md:1339-1344); x64 mode is enabled at import.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax

import eth_consensus_specs_tpu  # noqa: F401  (package import enables x64)
import jax.numpy as jnp
import numpy as np

U64 = jnp.uint64


@dataclass(frozen=True)
class EpochParams:
    """Compile-time preset constants (static under jit; one compiled
    executable per preset). Values per presets/<p>/phase0.yaml."""

    effective_balance_increment: int
    base_reward_factor: int
    base_rewards_per_epoch: int
    proposer_reward_quotient: int
    min_epochs_to_inactivity_penalty: int
    inactivity_penalty_quotient: int
    proportional_slashing_multiplier: int
    epochs_per_slashings_vector: int
    hysteresis_quotient: int
    hysteresis_downward_multiplier: int
    hysteresis_upward_multiplier: int
    max_effective_balance: int

    @classmethod
    def from_spec(cls, spec) -> "EpochParams":
        return cls(
            effective_balance_increment=spec.EFFECTIVE_BALANCE_INCREMENT,
            base_reward_factor=spec.BASE_REWARD_FACTOR,
            base_rewards_per_epoch=spec.BASE_REWARDS_PER_EPOCH,
            proposer_reward_quotient=spec.PROPOSER_REWARD_QUOTIENT,
            min_epochs_to_inactivity_penalty=spec.MIN_EPOCHS_TO_INACTIVITY_PENALTY,
            inactivity_penalty_quotient=spec.INACTIVITY_PENALTY_QUOTIENT,
            proportional_slashing_multiplier=spec.PROPORTIONAL_SLASHING_MULTIPLIER,
            epochs_per_slashings_vector=spec.EPOCHS_PER_SLASHINGS_VECTOR,
            hysteresis_quotient=spec.HYSTERESIS_QUOTIENT,
            hysteresis_downward_multiplier=spec.HYSTERESIS_DOWNWARD_MULTIPLIER,
            hysteresis_upward_multiplier=spec.HYSTERESIS_UPWARD_MULTIPLIER,
            max_effective_balance=spec.MAX_EFFECTIVE_BALANCE,
        )


class EpochColumns(NamedTuple):
    """Columnar validator registry + previous-epoch participation.

    Per-validator arrays (length N). Participation masks are raw "attested
    for component X" bits; the kernel applies the unslashed filter itself.
    `incl_delay`/`incl_proposer` describe the earliest-included source
    attestation per attester (delay >= 1 everywhere; garbage where
    src_att is False — masked out).
    """

    effective_balance: jnp.ndarray  # u64[N]
    balance: jnp.ndarray  # u64[N]
    slashed: jnp.ndarray  # bool[N]
    activation_epoch: jnp.ndarray  # u64[N]
    exit_epoch: jnp.ndarray  # u64[N]
    withdrawable_epoch: jnp.ndarray  # u64[N]
    src_att: jnp.ndarray  # bool[N] prev-epoch matching-source attester
    tgt_att: jnp.ndarray  # bool[N] prev-epoch matching-target attester
    head_att: jnp.ndarray  # bool[N] prev-epoch matching-head attester
    cur_tgt_att: jnp.ndarray  # bool[N] current-epoch matching-target attester
    incl_delay: jnp.ndarray  # u64[N]
    incl_proposer: jnp.ndarray  # i64[N]


class JustificationState(NamedTuple):
    """Scalar fork-accounting state threaded through the kernel."""

    current_epoch: jnp.ndarray  # u64 scalar
    justification_bits: jnp.ndarray  # bool[4]
    prev_justified_epoch: jnp.ndarray  # u64
    prev_justified_root: jnp.ndarray  # u8[32]
    cur_justified_epoch: jnp.ndarray  # u64
    cur_justified_root: jnp.ndarray  # u8[32]
    finalized_epoch: jnp.ndarray  # u64
    finalized_root: jnp.ndarray  # u8[32]
    block_root_prev: jnp.ndarray  # u8[32] get_block_root(state, prev_epoch)
    block_root_cur: jnp.ndarray  # u8[32] get_block_root(state, cur_epoch)
    slashings_sum: jnp.ndarray  # u64 sum(state.slashings)


class EpochResult(NamedTuple):
    balance: jnp.ndarray
    effective_balance: jnp.ndarray
    justification_bits: jnp.ndarray
    prev_justified_epoch: jnp.ndarray
    prev_justified_root: jnp.ndarray
    cur_justified_epoch: jnp.ndarray
    cur_justified_root: jnp.ndarray
    finalized_epoch: jnp.ndarray
    finalized_root: jnp.ndarray
    rewards: jnp.ndarray  # attestation-delta rewards (parity debugging)
    penalties: jnp.ndarray  # attestation-delta penalties


def example_inputs(n_validators: int, epoch: int = 10, slashings_half_vector: int = 4096):
    """Deterministic, spec-plausible columnar state (no RNG seed games:
    fixed generator seed, valid ranges for every column).
    `slashings_half_vector` = EPOCHS_PER_SLASHINGS_VECTOR // 2 of the preset
    the columns will run under (mainnet 4096) — half the slashed validators
    land exactly inside the correlated-slashing penalty window so that term
    is live in every driver check."""
    rng = np.random.default_rng(1234)
    n = n_validators
    max_eff = np.uint64(32_000_000_000)
    incr = np.uint64(1_000_000_000)
    eff = (rng.integers(17, 33, n).astype(np.uint64)) * incr
    bal = eff + rng.integers(0, 10**9, n).astype(np.uint64)
    slashed = rng.random(n) < 0.01
    act = np.zeros(n, np.uint64)
    exitep = np.full(n, np.iinfo(np.uint64).max, np.uint64)
    exited = rng.random(n) < 0.02
    exitep[exited] = epoch - 1
    wd = np.full(n, np.iinfo(np.uint64).max, np.uint64)
    in_window = slashed & (rng.random(n) < 0.5)
    wd[slashed] = epoch + 4  # slashed but outside the penalty window
    wd[in_window] = epoch + slashings_half_vector  # penalty applies
    src = rng.random(n) < 0.9
    tgt = src & (rng.random(n) < 0.95)
    head = tgt & (rng.random(n) < 0.9)
    cur_tgt = rng.random(n) < 0.8
    delay = rng.integers(1, 9, n).astype(np.uint64)
    proposer = rng.integers(0, n, n)
    cols = EpochColumns(
        effective_balance=np.minimum(eff, max_eff),
        balance=bal,
        slashed=slashed,
        activation_epoch=act,
        exit_epoch=exitep,
        withdrawable_epoch=wd,
        src_att=src,
        tgt_att=tgt,
        head_att=head,
        cur_tgt_att=cur_tgt,
        incl_delay=delay,
        incl_proposer=proposer,
    )
    just = JustificationState(
        current_epoch=np.uint64(epoch),
        justification_bits=np.array([True, True, False, False]),
        prev_justified_epoch=np.uint64(epoch - 2),
        prev_justified_root=np.frombuffer(b"\x01" * 32, np.uint8),
        cur_justified_epoch=np.uint64(epoch - 1),
        cur_justified_root=np.frombuffer(b"\x02" * 32, np.uint8),
        finalized_epoch=np.uint64(epoch - 3),
        finalized_root=np.frombuffer(b"\x03" * 32, np.uint8),
        block_root_prev=np.frombuffer(b"\x04" * 32, np.uint8),
        block_root_cur=np.frombuffer(b"\x05" * 32, np.uint8),
        slashings_sum=np.uint64(64_000_000_000),
    )
    return cols, just


def isqrt_u64(x: jnp.ndarray) -> jnp.ndarray:
    """Largest r with r*r <= x, for uint64 x (spec integer_squareroot,
    reference: specs/phase0/beacon-chain.md:799-807). Float64 seed gives r
    within +-1 of exact for all x < 2**64; two correction passes each way."""
    r = jnp.minimum(
        jnp.sqrt(x.astype(jnp.float64)).astype(U64), jnp.asarray(0xFFFFFFFF, U64)
    )
    for _ in range(2):
        r = jnp.where((r > 0) & (r * r > x), r - 1, r)
    for _ in range(2):
        rp = r + 1
        ok = (rp <= jnp.asarray(0xFFFFFFFF, U64)) & (rp * rp <= x)
        r = jnp.where(ok, rp, r)
    return r


class LocalReductions:
    """Single-device reduction/scatter primitives. The sharded epoch path
    (parallel/epoch.py) swaps in psum-backed equivalents — the kernel body
    is identical on one chip and on a mesh; only these two ops change."""

    def sum(self, x: jnp.ndarray) -> jnp.ndarray:
        return jnp.sum(x)

    def scatter_add(self, idx: jnp.ndarray, amounts: jnp.ndarray, local_n: int) -> jnp.ndarray:
        """Sum `amounts` into a (globally sized) zero vector at global
        indices `idx`; return this shard's slice of the result."""
        return jnp.zeros(local_n, amounts.dtype).at[jnp.clip(idx, 0, local_n - 1)].add(amounts)


_LOCAL = LocalReductions()


def _total_balance(mask, eff, increment, red) -> jnp.ndarray:
    """max(EFFECTIVE_BALANCE_INCREMENT, sum of effective balances in mask)
    (reference: specs/phase0/beacon-chain.md get_total_balance)."""
    s = red.sum(jnp.where(mask, eff, jnp.zeros_like(eff)))
    return jnp.maximum(s, increment)


def justification_update(just, prev_tgt_bal, cur_tgt_bal, total_active):
    """Branch-free weigh_justification_and_finalization (reference:
    specs/phase0/beacon-chain.md:1466-1525) — identical scalar machine for
    every fork; only the target-balance inputs are fork-specific.

    Returns (bits, prev_je, prev_jr, cur_je, cur_jr, fin_e, fin_r) with the
    genesis guard (epoch <= 1 leaves everything unchanged) applied.
    """
    one = jnp.asarray(1, U64)
    cur_epoch = just.current_epoch
    prev_epoch = jnp.where(cur_epoch > 0, cur_epoch - one, jnp.asarray(0, U64))
    do_justif = cur_epoch > one

    old_bits = just.justification_bits
    old_prev_je, old_prev_jr = just.prev_justified_epoch, just.prev_justified_root
    old_cur_je, old_cur_jr = just.cur_justified_epoch, just.cur_justified_root

    just_prev = prev_tgt_bal * jnp.asarray(3, U64) >= total_active * jnp.asarray(2, U64)
    just_cur = cur_tgt_bal * jnp.asarray(3, U64) >= total_active * jnp.asarray(2, U64)

    # bits shift in one, newest first; then the two justification sets
    b0 = just_cur
    b1 = old_bits[0] | just_prev
    b2, b3 = old_bits[1], old_bits[2]
    new_bits = jnp.stack([b0, b1, b2, b3])

    new_cur_je = jnp.where(just_cur, cur_epoch, jnp.where(just_prev, prev_epoch, old_cur_je))
    new_cur_jr = jnp.where(
        just_cur,
        just.block_root_cur,
        jnp.where(just_prev, just.block_root_prev, old_cur_jr),
    )

    # finalization ladder — later (shorter-span) rules override earlier ones,
    # matching the sequential-if structure of weigh_justification_and_finalization
    fin_e, fin_r = just.finalized_epoch, just.finalized_root
    c234 = b1 & b2 & b3 & (old_prev_je + jnp.asarray(3, U64) == cur_epoch)
    fin_e = jnp.where(c234, old_prev_je, fin_e)
    fin_r = jnp.where(c234, old_prev_jr, fin_r)
    c23 = b1 & b2 & (old_prev_je + jnp.asarray(2, U64) == cur_epoch)
    fin_e = jnp.where(c23, old_prev_je, fin_e)
    fin_r = jnp.where(c23, old_prev_jr, fin_r)
    c123 = b0 & b1 & b2 & (old_cur_je + jnp.asarray(2, U64) == cur_epoch)
    fin_e = jnp.where(c123, old_cur_je, fin_e)
    fin_r = jnp.where(c123, old_cur_jr, fin_r)
    c12 = b0 & b1 & (old_cur_je + one == cur_epoch)
    fin_e = jnp.where(c12, old_cur_je, fin_e)
    fin_r = jnp.where(c12, old_cur_jr, fin_r)

    return (
        jnp.where(do_justif, new_bits, old_bits),
        jnp.where(do_justif, old_cur_je, old_prev_je),
        jnp.where(do_justif, old_cur_jr, old_prev_jr),
        jnp.where(do_justif, new_cur_je, old_cur_je),
        jnp.where(do_justif, new_cur_jr, old_cur_jr),
        jnp.where(do_justif, fin_e, just.finalized_epoch),
        jnp.where(do_justif, fin_r, just.finalized_root),
    )


def epoch_accounting_impl(
    params: EpochParams,
    cols: EpochColumns,
    just: JustificationState,
    red: LocalReductions = _LOCAL,
) -> EpochResult:
    """The fused accounting epoch: justification/finalization, attestation
    rewards & penalties, slashing penalties, effective-balance hysteresis.

    Everything is branch-free; genesis-epoch guards are `where` masks so a
    single compiled executable serves every epoch.
    """
    p = params
    n = cols.balance.shape[0]
    one = jnp.asarray(1, U64)
    zero = jnp.asarray(0, U64)
    incr = jnp.asarray(p.effective_balance_increment, U64)

    cur_epoch = just.current_epoch
    prev_epoch = jnp.where(cur_epoch > 0, cur_epoch - one, zero)

    eff = cols.effective_balance
    not_slashed = ~cols.slashed
    active_cur = (cols.activation_epoch <= cur_epoch) & (cur_epoch < cols.exit_epoch)
    active_prev = (cols.activation_epoch <= prev_epoch) & (prev_epoch < cols.exit_epoch)
    eligible = active_prev | (cols.slashed & (prev_epoch + one < cols.withdrawable_epoch))

    total_active = _total_balance(active_cur, eff, incr, red)

    # -- justification & finalization (scalar; skipped for epochs 0,1) ----
    prev_tgt_bal = _total_balance(cols.tgt_att & not_slashed, eff, incr, red)
    cur_tgt_bal = _total_balance(cols.cur_tgt_att & not_slashed, eff, incr, red)
    (
        out_bits,
        out_prev_je,
        out_prev_jr,
        out_cur_je,
        out_cur_jr,
        out_fin_e,
        out_fin_r,
    ) = justification_update(just, prev_tgt_bal, cur_tgt_bal, total_active)

    # -- rewards & penalties (uses the POST-justification finalized epoch) --
    sqrt_total = isqrt_u64(total_active)
    base_reward = (
        eff
        * jnp.asarray(p.base_reward_factor, U64)
        // sqrt_total
        // jnp.asarray(p.base_rewards_per_epoch, U64)
    )
    proposer_reward = base_reward // jnp.asarray(p.proposer_reward_quotient, U64)

    finality_delay = prev_epoch - out_fin_e
    in_leak = finality_delay > jnp.asarray(p.min_epochs_to_inactivity_penalty, U64)

    rewards = jnp.zeros(n, U64)
    penalties = jnp.zeros(n, U64)
    total_units = total_active // incr
    for mask in (cols.src_att, cols.tgt_att, cols.head_att):
        att = mask & not_slashed
        att_bal = _total_balance(att, eff, incr, red)
        # during leaks attesters are credited as if participation were optimal
        full = jnp.where(in_leak, base_reward, base_reward * (att_bal // incr) // total_units)
        rewards = rewards + jnp.where(eligible & att, full, zero)
        penalties = penalties + jnp.where(eligible & ~att, base_reward, zero)

    # inclusion-delay micro-rewards: attester share decays with delay,
    # proposer share scatter-added at the earliest includer
    src_unslashed = cols.src_att & not_slashed
    att_share = jnp.where(
        src_unslashed, (base_reward - proposer_reward) // jnp.maximum(cols.incl_delay, one), zero
    )
    rewards = rewards + att_share
    prop_amount = jnp.where(src_unslashed, proposer_reward, zero)
    rewards = rewards + red.scatter_add(cols.incl_proposer, prop_amount, n)

    # inactivity leak: quadratic drain on non-target-attesting eligibles
    leak_base = jnp.where(
        eligible & in_leak,
        jnp.asarray(p.base_rewards_per_epoch, U64) * base_reward - proposer_reward,
        zero,
    )
    tgt_unslashed = cols.tgt_att & not_slashed
    leak_extra = jnp.where(
        eligible & in_leak & ~tgt_unslashed,
        eff * finality_delay // jnp.asarray(p.inactivity_penalty_quotient, U64),
        zero,
    )
    penalties = penalties + leak_base + leak_extra

    do_rewards = cur_epoch > zero
    rewards = jnp.where(do_rewards, rewards, jnp.zeros_like(rewards))
    penalties = jnp.where(do_rewards, penalties, jnp.zeros_like(penalties))

    bal = cols.balance + rewards
    bal = bal - jnp.minimum(bal, penalties)

    # -- slashings sweep (runs every epoch, no genesis guard) -------------
    adj_slash = jnp.minimum(
        just.slashings_sum * jnp.asarray(p.proportional_slashing_multiplier, U64),
        total_active,
    )
    half_vec = jnp.asarray(p.epochs_per_slashings_vector // 2, U64)
    slash_now = cols.slashed & (cur_epoch + half_vec == cols.withdrawable_epoch)
    slash_penalty = (eff // incr) * adj_slash // total_active * incr
    bal = bal - jnp.minimum(bal, jnp.where(slash_now, slash_penalty, zero))

    # -- effective-balance hysteresis -------------------------------------
    hyst = incr // jnp.asarray(p.hysteresis_quotient, U64)
    down = hyst * jnp.asarray(p.hysteresis_downward_multiplier, U64)
    up = hyst * jnp.asarray(p.hysteresis_upward_multiplier, U64)
    crossed = (bal + down < eff) | (eff + up < bal)
    new_eff = jnp.where(
        crossed,
        jnp.minimum(bal - bal % incr, jnp.asarray(p.max_effective_balance, U64)),
        eff,
    )

    return EpochResult(
        balance=bal,
        effective_balance=new_eff,
        justification_bits=out_bits,
        prev_justified_epoch=out_prev_je,
        prev_justified_root=out_prev_jr,
        cur_justified_epoch=out_cur_je,
        cur_justified_root=out_cur_jr,
        finalized_epoch=out_fin_e,
        finalized_root=out_fin_r,
        rewards=rewards,
        penalties=penalties,
    )


epoch_accounting = partial(jax.jit, static_argnums=(0,))(epoch_accounting_impl)
