"""Closed loop of whole-registry state roots through `submit_state_root`.

Set-up makes, from the seed, the static tree content of an n-validator
Altair state (random static validator nodes and field roots, as
`ops/state_root.synthetic_static` does, but here, so that the reference is
handed nothing the program made), puts it on the device once, and makes
`column_sets` sets of the three dynamic columns on the host. Each request
hands one set over as host arrays, the client's bytes (24 bytes a validator),
cycling so that no two consecutive requests are equal. Every seed draws the
same sizes; only the values and the order differ.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import state_root_ref as ref

DYNAMIC = (
    "validators", "balances", "previous_epoch_participation",
    "current_epoch_participation", "justification_bits",
    "previous_justified_checkpoint", "current_justified_checkpoint",
    "finalized_checkpoint", "inactivity_scores",
)
GWEI = 10**9


def _words(rows: int, rng) -> np.ndarray:
    return rng.integers(0, 1 << 32, size=(rows, 8), dtype=np.uint64).astype(np.uint32)


class Traffic:
    def __init__(self, config: dict, params: dict, seed: int):
        self.n = int(config["validators"])
        self.sets = int(params["column_sets"])
        self.timeout = float(params.get("timeout_s", 600))
        self.seed = seed
        self.answers: list[tuple[int, bytes]] = []

    # -- inputs ---------------------------------------------------------------

    def make_inputs(self) -> None:
        rng = np.random.default_rng([self.seed, 0x57A7E])
        n = self.n
        slashed = np.zeros((n, 8), np.uint32)
        slashed[rng.random(n) < 0.001, 0] = 0x01000000  # SSZ chunk of `true`
        self.static = {
            "val_node_a": _words(n, rng),
            "val_node_f": _words(n, rng),
            "slashed_chunk": slashed,
            "prev_part_flags": rng.integers(0, 8, size=n, dtype=np.int64).astype(np.uint8),
            "top_chunks": _words(1 << ref.TOP_DEPTH, rng),
        }
        self.columns = []
        for _ in range(self.sets):
            balance = (32 * GWEI + rng.integers(-2 * GWEI, 2 * GWEI, n)).astype(np.uint64)
            effective = np.minimum(balance // GWEI, 32).astype(np.uint64) * np.uint64(GWEI)
            self.columns.append((balance, effective, rng.integers(0, 50, n).astype(np.uint64)))
        epoch = int(rng.integers(10, 1 << 20))
        self.just = {
            "current_epoch": np.uint64(epoch),
            "justification_bits": rng.random(4) < 0.5,
            "prev_justified_epoch": np.uint64(epoch - 2),
            "prev_justified_root": rng.integers(0, 256, 32).astype(np.uint8),
            "cur_justified_epoch": np.uint64(epoch - 1),
            "cur_justified_root": rng.integers(0, 256, 32).astype(np.uint8),
            "finalized_epoch": np.uint64(epoch - 3),
            "finalized_root": rng.integers(0, 256, 32).astype(np.uint8),
            "block_root_prev": rng.integers(0, 256, 32).astype(np.uint8),
            "block_root_cur": rng.integers(0, 256, 32).astype(np.uint8),
            "slashings_sum": np.uint64(0),
        }
        self.order = [int(i) for i in rng.permutation(self.sets)]

    def setup(self, svc) -> None:
        import jax

        from eth_consensus_specs_tpu.ops.state_columns import JustificationState
        from eth_consensus_specs_tpu.ops.state_root import StateRootArrays, StateRootMeta

        self.make_inputs()
        zero_words = np.stack([np.frombuffer(z, ">u4").astype(np.uint32) for z in ref.ZERO])
        self.arrays = StateRootArrays(
            **{k: jax.device_put(v) for k, v in self.static.items()},
            zerohashes=jax.device_put(zero_words),
        )
        self.meta = StateRootMeta(
            dynamic_slots=tuple((i, f) for i, f in enumerate(ref.FIELDS) if f in DYNAMIC),
            n_validators=self.n,
            top_depth=ref.TOP_DEPTH,
        )
        self.just_in = JustificationState(**self.just)
        # every set once: the one program compiles or loads, each host buffer
        # has crossed to the device once
        for k in range(self.sets):
            self._submit(svc, k)

    # -- the loop -------------------------------------------------------------

    def _submit(self, svc, k: int) -> bytes:
        from jax.profiler import TraceAnnotation

        balance, effective, inactivity = self.columns[k]
        with TraceAnnotation("client.submit"):
            fut = svc.submit_state_root(
                self.arrays, self.meta, balance, effective, inactivity, self.just_in
            )
        with TraceAnnotation("client.wait_verdict"):
            words = fut.result(timeout=self.timeout)
        return np.asarray(words).astype(">u4").tobytes()

    def request(self, svc, i: int) -> None:
        k = self.order[i % self.sets]
        self.answers.append((k, self._submit(svc, k)))

    def release(self) -> None:
        self.arrays = None

    # -- correct --------------------------------------------------------------

    def reference_roots(self, stale_registry: bool = False) -> dict[int, bytes]:
        """The reference's root of each column set the window used. The
        control (`stale_registry`) keeps the validators' list root of the set
        served just before: a re-root that skips the registry because
        effective balances rarely move."""
        used = sorted({k for k, _ in self.answers})
        source = {k: self.order[self.order.index(k) - 1] if stale_registry else k for k in used}
        registry = {
            k: ref.registry_root(self.static, self.columns[k][1]) for k in set(source.values())
        }
        return {
            k: ref.state_root(self.static, *self.columns[k], self.just, registry=registry[source[k]])
            for k in used
        }

    def compare(self, control: bool = False) -> dict:
        want = self.reference_roots()
        got = self.reference_roots(stale_registry=True) if control else None
        wrong = sum(
            (got[k] if control else root) != want[k] for k, root in self.answers
        )
        return {"roots_wrong": (wrong, 0)}
