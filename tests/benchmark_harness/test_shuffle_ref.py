"""The committee cell's plain reference: its two forms against each other,
against the spec object's `compute_shuffled_index`, and its independence
of the program."""

from __future__ import annotations

import ast
import os

import numpy as np
import pytest

from benchmark import run
from benchmark.reference import shuffle_ref as ref


def _epoch(n: int, tag: int):
    rng = np.random.default_rng([n, tag])
    active = np.sort(rng.choice(max(2 * n, 8), n, replace=False))
    return active, rng.bytes(32)


@pytest.mark.parametrize("n", [1, 2, 7, 255, 256, 257, 1000, 4096])
def test_the_whole_list_form_is_the_per_index_form_at_every_position(n):
    active, seed = _epoch(n, 1)
    got = ref.shuffled_list(active, seed)
    positions = range(n) if n <= 1000 else np.random.default_rng(n).integers(0, n, 256)
    for i in positions:
        assert got[i] == active[ref.compute_shuffled_index(int(i), n, seed)]
    assert sorted(got.tolist()) == active.tolist()  # a rearrangement of its input


@pytest.mark.parametrize("n", [1, 2, 7, 255, 256, 257, 1000])
def test_the_per_index_form_is_the_spec_objects(n):
    from eth_consensus_specs_tpu.forks import get_spec

    spec = get_spec("phase0", "mainnet")
    assert spec.SHUFFLE_ROUND_COUNT == ref.SHUFFLE_ROUND_COUNT == 90
    _, seed = _epoch(n, 2)
    for i in np.random.default_rng(n).integers(0, n, min(n, 48)):
        assert ref.compute_shuffled_index(int(i), n, seed) == spec.compute_shuffled_index(
            int(i), n, seed)


def test_fewer_rounds_is_the_minimal_presets_shuffle():
    from eth_consensus_specs_tpu.forks import get_spec

    spec = get_spec("phase0", "minimal")
    active, seed = _epoch(300, 3)
    got = ref.shuffled_list(active, seed, rounds=spec.SHUFFLE_ROUND_COUNT)
    assert got.tolist() == [active[spec.compute_shuffled_index(i, 300, seed)] for i in range(300)]
    assert got.tolist() != ref.shuffled_list(active, seed).tolist()


def test_the_input_is_left_as_it_came_and_an_empty_or_single_list_is_itself():
    active, seed = _epoch(500, 4)
    before = active.copy()
    ref.shuffled_list(active, seed)
    assert np.array_equal(active, before)
    assert ref.shuffled_list(np.array([], np.int64), seed).tolist() == []
    assert ref.shuffled_list(np.array([9]), seed).tolist() == [9]


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(run.ROOT, "benchmark", "reference", "shuffle_ref.py")
    tree = ast.parse(open(path).read())
    modules = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            modules |= {alias.name.split(".")[0] for alias in node.names}
        elif isinstance(node, ast.ImportFrom):
            modules.add((node.module or "").split(".")[0])
    assert modules == {"__future__", "hashlib", "numpy"}
