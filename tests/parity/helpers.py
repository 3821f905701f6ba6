"""Differential-parity plumbing.

Each case runs the same scenario through two independent executables:

* ``spec``  — this framework's class-based fork spec (forks/),
* ``ref``   — the reference's markdown, compiled by specc/ straight from
  /root/reference/specs (the normative text IS the oracle; the
  reference's own pyspec is this same text run through pysetup).

State/objects cross the boundary as SSZ bytes, and agreement is asserted
on the OUTCOME (valid/invalid) and, for valid transitions, on the
byte-identical ``hash_tree_root`` of the post-state — BASELINE.json's
"bit-exact reftest parity" gate, evidenced case by case.
"""

from __future__ import annotations

import os
from functools import lru_cache

import pytest

from eth_consensus_specs_tpu import ssz
from eth_consensus_specs_tpu.forks import get_spec
from eth_consensus_specs_tpu.specc import compile_fork, compiled_forks
from eth_consensus_specs_tpu.specc.compiler import REFERENCE_SPECS
from eth_consensus_specs_tpu.test_infra.genesis import create_genesis_state
from eth_consensus_specs_tpu.utils import bls

PARITY_FORKS = compiled_forks()  # phase0 .. gloas

# Preset axis: the reference builds every fork x {minimal, mainnet}
# (reference Makefile:5-17). test_parity.py runs under minimal; the
# mainnet re-collection module flips this seam for the same cases.
_CURRENT_PRESET = "minimal"


class preset_override:
    def __init__(self, preset: str):
        self.preset = preset

    def __enter__(self):
        global _CURRENT_PRESET
        self._prev = _CURRENT_PRESET
        _CURRENT_PRESET = self.preset

    def __exit__(self, *exc):
        global _CURRENT_PRESET
        _CURRENT_PRESET = self._prev


def current_preset() -> str:
    return _CURRENT_PRESET


def require_reference() -> None:
    """Skip where the reference checkout is not mounted. Keyed on the
    DIRECTORY: a pinned file missing from a tree that is there still
    raises in specc (`_require_absent_unpinned`) and fails the test."""
    if not os.path.isdir(REFERENCE_SPECS):
        pytest.skip(
            f"no reference consensus-specs tree at {REFERENCE_SPECS} "
            "(ETH_SPECS_REFERENCE): specc has no markdown to compile the oracle from"
        )


def specs(fork: str, preset: str | None = None):
    """(class-spec, compiled-reference-spec) pair for a fork."""
    require_reference()
    return _specs(fork, preset or _CURRENT_PRESET)


@lru_cache(maxsize=None)
def _specs(fork: str, preset: str):
    return get_spec(fork, preset), compile_fork(fork, preset)


def genesis_state(fork: str):
    """Fresh framework-side genesis state (deserialized from the cached
    serialization, so mutation in one test never leaks into another)."""
    spec, _ = specs(fork)
    return ssz.deserialize(spec.BeaconState, _genesis_bytes(fork, _CURRENT_PRESET))


@lru_cache(maxsize=None)
def _genesis_bytes(fork: str, preset: str, n_validators: int = 64) -> bytes:
    spec, _ = specs(fork, preset)
    prev = bls.bls_active
    bls.bls_active = False
    try:
        state = create_genesis_state(
            spec, [spec.MAX_EFFECTIVE_BALANCE] * n_validators, spec.MAX_EFFECTIVE_BALANCE
        )
    finally:
        bls.bls_active = prev
    return bytes(ssz.serialize(state))


def to_ref(ref, obj, type_name: str | None = None):
    """Move an object across the boundary as SSZ bytes."""
    name = type_name or type(obj).__name__.split("[")[0]
    ref_type = getattr(ref, name)
    return ssz.deserialize(ref_type, ssz.serialize(obj))


def roots_equal(ours, ref_mod, theirs) -> bool:
    return bytes(ssz.hash_tree_root(ours)) == bytes(ref_mod.hash_tree_root(theirs))


_SPEC_FAILURES = (AssertionError, IndexError, ValueError, ZeroDivisionError, KeyError)


def run_both(spec, ref, state, callable_name: str, *args, ref_args=None):
    """Run ``spec.<name>(state, *args)`` and ``ref.<name>(ref_state, ...)``;
    assert same outcome; on success assert byte-identical post-state roots.
    Returns (outcome_ok, our_post_state)."""
    ref_state = to_ref(ref, state, "BeaconState")
    if ref_args is None:
        ref_args = [to_ref(ref, a) if isinstance(a, ssz.View) else a for a in args]
    ours = state.copy()
    ok_ours, err_ours = True, None
    try:
        getattr(spec, callable_name)(ours, *args)
    except _SPEC_FAILURES as e:
        ok_ours, err_ours = False, e
    ok_ref, err_ref = True, None
    try:
        getattr(ref, callable_name)(ref_state, *ref_args)
    except _SPEC_FAILURES as e:
        ok_ref, err_ref = False, e
    assert ok_ours == ok_ref, (
        f"{callable_name}: outcome diverged — ours={'ok' if ok_ours else err_ours!r} "
        f"ref={'ok' if ok_ref else err_ref!r}"
    )
    if ok_ours:
        assert roots_equal(ours, ref, ref_state), f"{callable_name}: post-state roots diverge"
    return ok_ours, ours


def forks_from(first: str) -> list[str]:
    return PARITY_FORKS[PARITY_FORKS.index(first) :]


def parametrize_forks(first: str = "phase0"):
    return pytest.mark.parametrize("fork", forks_from(first))
