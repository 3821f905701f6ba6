"""Columnar altair+ epoch accounting — ONE fused XLA computation.

Altair replaced phase0's pending-attestation reward pipeline with
participation FLAGS (reference: specs/altair/beacon-chain.md:398-486,687):
per-validator uint8 bitfields that are *already columnar in the state*.
The accounting epoch is therefore an even cleaner fusion than phase0's:

    justification/finalization  (flag-derived target balances)
    inactivity-score updates    (bias/recovery integrator per validator)
    flag-weight rewards         (3 components, sequential clamped apply)
    inactivity penalties        (score-proportional, uses UPDATED scores)
    slashings sweep             (altair multiplier)
    effective-balance hysteresis

in one jitted function over flag/score/balance columns. All control flow is
`jnp.where`; the same fusion-boundary proof as phase0 applies to
process_registry_updates (it never touches balance columns or the slashing
predicate — see ops/state_columns.py docstring).

RAW-KERNEL fork coverage: altair through electra+ semantics — the two
quotient knobs enter via the spec's fork hooks, electra's per-increment
slashing rounding via `electra_slashing`, and EIP-7251's per-validator
MaxEB as an optional column. The SPEC-LEVEL columnar wrapper
(`process_epoch_columnar`) covers altair→electra and IS the default
process_epoch: altair→deneb run the full fusion; electra runs the
TWO-PHASE split (`altair_epoch_accounting_phase_a` without the
effective-balance step, host-side pending deposit/consolidation queues in
spec order, hysteresis after — forks/electra.py process_epoch_columnar).

Sequential balance application (reward_k then clamped penalty_k, k over
src/tgt/head/inactivity) exactly mirrors the object path's delta-list
loop, so clamp-at-zero edge cases are bit-exact.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax

import eth_consensus_specs_tpu  # noqa: F401  (package import enables x64)
import jax.numpy as jnp
import numpy as np

from .state_columns import (
    JustificationState,
    LocalReductions,
    _LOCAL,
    _total_balance,
    example_inputs,
    isqrt_u64,
    justification_update,
)


def _is_post_electra(spec) -> bool:
    from eth_consensus_specs_tpu.config import is_post_fork

    return is_post_fork(spec.fork_name, "electra")


U64 = jnp.uint64


@dataclass(frozen=True)
class AltairEpochParams:
    """Compile-time constants (static under jit). Weights in flag order
    (source, target, head) per PARTICIPATION_FLAG_WEIGHTS."""

    effective_balance_increment: int
    base_reward_factor: int
    weights: tuple  # (TIMELY_SOURCE_WEIGHT, TIMELY_TARGET_WEIGHT, TIMELY_HEAD_WEIGHT)
    weight_denominator: int
    timely_head_flag_index: int
    min_epochs_to_inactivity_penalty: int
    inactivity_score_bias: int
    inactivity_score_recovery_rate: int
    inactivity_penalty_quotient: int  # fork hook value (altair/bellatrix+)
    proportional_slashing_multiplier: int  # fork hook value
    epochs_per_slashings_vector: int
    hysteresis_quotient: int
    hysteresis_downward_multiplier: int
    hysteresis_upward_multiplier: int
    max_effective_balance: int
    # [Electra:EIP7251] per-increment penalty quantum replaces altair's
    # per-validator rounding (specs/electra/beacon-chain.md:893-920); the
    # per-validator effective-balance ceiling moves into a column
    electra_slashing: bool = False

    @classmethod
    def from_spec(cls, spec) -> "AltairEpochParams":
        return cls(
            effective_balance_increment=spec.EFFECTIVE_BALANCE_INCREMENT,
            base_reward_factor=spec.BASE_REWARD_FACTOR,
            weights=tuple(int(w) for w in spec.PARTICIPATION_FLAG_WEIGHTS),
            weight_denominator=spec.WEIGHT_DENOMINATOR,
            timely_head_flag_index=spec.TIMELY_HEAD_FLAG_INDEX,
            min_epochs_to_inactivity_penalty=spec.MIN_EPOCHS_TO_INACTIVITY_PENALTY,
            inactivity_score_bias=spec.config.INACTIVITY_SCORE_BIAS,
            inactivity_score_recovery_rate=spec.config.INACTIVITY_SCORE_RECOVERY_RATE,
            inactivity_penalty_quotient=spec.inactivity_penalty_quotient(),
            proportional_slashing_multiplier=spec.proportional_slashing_multiplier(),
            epochs_per_slashings_vector=spec.EPOCHS_PER_SLASHINGS_VECTOR,
            hysteresis_quotient=spec.HYSTERESIS_QUOTIENT,
            hysteresis_downward_multiplier=spec.HYSTERESIS_DOWNWARD_MULTIPLIER,
            hysteresis_upward_multiplier=spec.HYSTERESIS_UPWARD_MULTIPLIER,
            max_effective_balance=spec.MAX_EFFECTIVE_BALANCE,
            electra_slashing=_is_post_electra(spec),
        )


class AltairEpochColumns(NamedTuple):
    """Columnar registry + participation flags + inactivity scores."""

    effective_balance: jnp.ndarray  # u64[N]
    balance: jnp.ndarray  # u64[N]
    slashed: jnp.ndarray  # bool[N]
    activation_epoch: jnp.ndarray  # u64[N]
    exit_epoch: jnp.ndarray  # u64[N]
    withdrawable_epoch: jnp.ndarray  # u64[N]
    prev_flags: jnp.ndarray  # u8[N] previous_epoch_participation bitfield
    cur_tgt_att: jnp.ndarray  # bool[N] current-epoch TIMELY_TARGET flag
    inactivity_scores: jnp.ndarray  # u64[N]
    # [Electra:EIP7251] per-validator effective-balance ceiling (32 ETH or
    # 2048 ETH by credential type); None pre-electra -> the scalar param
    max_effective_balance: jnp.ndarray | None = None  # u64[N]


class AltairEpochResult(NamedTuple):
    balance: jnp.ndarray
    effective_balance: jnp.ndarray
    inactivity_scores: jnp.ndarray
    justification_bits: jnp.ndarray
    prev_justified_epoch: jnp.ndarray
    prev_justified_root: jnp.ndarray
    cur_justified_epoch: jnp.ndarray
    cur_justified_root: jnp.ndarray
    finalized_epoch: jnp.ndarray
    finalized_root: jnp.ndarray


def example_altair_inputs(n_validators: int, epoch: int = 10, electra: bool = False):
    """Columnar altair+ state: flags/scores instead of attestation masks.
    With `electra`, a per-validator MaxEB column (EIP-7251 compounding:
    ~10% of validators at the 2048 ETH ceiling) rides along."""
    cols, just = example_inputs(n_validators, epoch=epoch)
    rng = np.random.default_rng(4321)
    n = n_validators
    prev_flags = (
        rng.integers(0, 2, n) * 1 + rng.integers(0, 2, n) * 2 + rng.integers(0, 2, n) * 4
    ).astype(np.uint8)
    max_eff = None
    if electra:
        compounding = rng.random(n) < 0.1
        max_eff = np.where(
            compounding, np.uint64(2_048_000_000_000), np.uint64(32_000_000_000)
        ).astype(np.uint64)
    acols = AltairEpochColumns(
        effective_balance=cols.effective_balance,
        balance=cols.balance,
        slashed=cols.slashed,
        activation_epoch=cols.activation_epoch,
        exit_epoch=cols.exit_epoch,
        withdrawable_epoch=cols.withdrawable_epoch,
        prev_flags=prev_flags,
        cur_tgt_att=cols.cur_tgt_att,
        inactivity_scores=rng.integers(0, 50, n).astype(np.uint64),
        max_effective_balance=max_eff,
    )
    return acols, just


def altair_epoch_accounting_impl(
    params: AltairEpochParams,
    cols: AltairEpochColumns,
    just: JustificationState,
    red: LocalReductions = _LOCAL,
    include_effective_balance: bool = True,
) -> AltairEpochResult:
    p = params
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

    # unslashed participating masks per flag (previous epoch)
    flags = cols.prev_flags.astype(jnp.uint32)
    part = [
        active_prev & (((flags >> k) & 1) == 1) & not_slashed
        for k in range(len(p.weights))
    ]

    # -- justification & finalization -------------------------------------
    prev_tgt_bal = _total_balance(part[1], eff, incr, red)
    cur_tgt_bal = _total_balance(active_cur & cols.cur_tgt_att & not_slashed, eff, incr, red)
    (
        out_bits,
        out_prev_je,
        out_prev_jr,
        out_cur_je,
        out_cur_jr,
        out_fin_e,
        out_fin_r,
    ) = justification_update(just, prev_tgt_bal, cur_tgt_bal, total_active)

    finality_delay = prev_epoch - out_fin_e
    in_leak = finality_delay > jnp.asarray(p.min_epochs_to_inactivity_penalty, U64)

    # -- inactivity-score updates (uses POST-justification leak state) ----
    participating_tgt = part[1]
    score = cols.inactivity_scores
    score = jnp.where(
        eligible,
        jnp.where(
            participating_tgt,
            score - jnp.minimum(one, score),
            score + jnp.asarray(p.inactivity_score_bias, U64),
        ),
        score,
    )
    score = jnp.where(
        eligible & ~in_leak,
        score - jnp.minimum(jnp.asarray(p.inactivity_score_recovery_rate, U64), score),
        score,
    )
    do_accounting = cur_epoch > zero
    score_out = jnp.where(do_accounting, score, cols.inactivity_scores)

    # -- rewards & penalties ----------------------------------------------
    brpi = incr * jnp.asarray(p.base_reward_factor, U64) // isqrt_u64(total_active)
    base_reward = (eff // incr) * brpi
    active_increments = total_active // incr
    wd = jnp.asarray(p.weight_denominator, U64)

    bal = cols.balance
    for k, weight_int in enumerate(p.weights):
        weight = jnp.asarray(weight_int, U64)
        pk_mask = part[k]
        part_increments = _total_balance(pk_mask, eff, incr, red) // incr
        reward = base_reward * weight * part_increments // (active_increments * wd)
        r_k = jnp.where(
            do_accounting & eligible & pk_mask & ~in_leak, reward, zero
        )
        if k != p.timely_head_flag_index:
            pen_k = jnp.where(
                do_accounting & eligible & ~pk_mask, base_reward * weight // wd, zero
            )
        else:
            pen_k = jnp.zeros_like(bal)
        bal = bal + r_k
        bal = bal - jnp.minimum(bal, pen_k)

    # inactivity penalties, proportional to the UPDATED scores
    pen_inact = (
        eff
        * score_out
        // jnp.asarray(p.inactivity_score_bias * p.inactivity_penalty_quotient, U64)
    )
    p_inact = jnp.where(do_accounting & eligible & ~participating_tgt, pen_inact, zero)
    bal = bal - jnp.minimum(bal, p_inact)

    # -- slashings sweep ---------------------------------------------------
    adj_slash = jnp.minimum(
        just.slashings_sum * jnp.asarray(p.proportional_slashing_multiplier, U64),
        total_active,
    )
    half_vec = jnp.asarray(p.epochs_per_slashings_vector // 2, U64)
    slash_now = cols.slashed & (cur_epoch + half_vec == cols.withdrawable_epoch)
    if p.electra_slashing:
        # [Electra:EIP7251] shared per-increment quantum, then scale by the
        # validator's increments (different rounding from altair)
        penalty_per_increment = adj_slash // (total_active // incr)
        slash_penalty = penalty_per_increment * (eff // incr)
    else:
        slash_penalty = (eff // incr) * adj_slash // total_active * incr
    bal = bal - jnp.minimum(bal, jnp.where(slash_now, slash_penalty, zero))

    # -- effective-balance hysteresis -------------------------------------
    # electra's TWO-PHASE split runs this step host-side AFTER the pending
    # deposit/consolidation queues (spec ordering,
    # specs/electra/beacon-chain.md:943,1022) — phase A returns eff as-is
    if include_effective_balance:
        hyst = incr // jnp.asarray(p.hysteresis_quotient, U64)
        down = hyst * jnp.asarray(p.hysteresis_downward_multiplier, U64)
        up = hyst * jnp.asarray(p.hysteresis_upward_multiplier, U64)
        crossed = (bal + down < eff) | (eff + up < bal)
        eff_ceiling = (
            cols.max_effective_balance
            if cols.max_effective_balance is not None
            else jnp.asarray(p.max_effective_balance, U64)
        )
        new_eff = jnp.where(crossed, jnp.minimum(bal - bal % incr, eff_ceiling), eff)
    else:
        new_eff = eff

    return AltairEpochResult(
        balance=bal,
        effective_balance=new_eff,
        inactivity_scores=score_out,
        justification_bits=out_bits,
        prev_justified_epoch=out_prev_je,
        prev_justified_root=out_prev_jr,
        cur_justified_epoch=out_cur_je,
        cur_justified_root=out_cur_jr,
        finalized_epoch=out_fin_e,
        finalized_root=out_fin_r,
    )


altair_epoch_accounting = partial(jax.jit, static_argnums=(0,))(altair_epoch_accounting_impl)
# phase A of the electra two-phase fusion: accounting without the
# effective-balance hysteresis (that runs after the host-side queues)
altair_epoch_accounting_phase_a = partial(
    jax.jit, static_argnums=(0,), static_argnames=("include_effective_balance",)
)(altair_epoch_accounting_impl)
