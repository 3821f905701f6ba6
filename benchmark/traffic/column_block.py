"""Closed loop of Fulu blocks as a supernode sees them: a block's data
column sidecars submitted together through `submit_column_verify`, the
next block when all verdicts are back.

Set-up makes a pool of blobs from the seed and, by the testing setup's
public trapdoor (benchmark/reference/das_ref.py: half a second a blob,
nothing of the program), each blob's commitment, 128 cells and 128
proofs. A block draws `blobs_per_block` of the pool by a permutation
seeded with the block's number and carries `columns_per_block` sidecars,
one a column (all 128 for a supernode, in an order the seed shuffles, as
gossip does not sort them; fewer are the first so many of that order):
sidecar `c` is the block's cells and proofs of column `c` and its
commitments, which all of a block's sidecars share. Block
`invalid_first` of the window, and no other, carries ONE cell with a
wrong proof in one sidecar of EACH half of its flush: the same blob's
proof for the next column, a well-formed point that opens another cell.
Without them every right answer is True, and a verifier that checks
nothing, or nothing in one half of a flush, would compare as correct.
The seed draws the sidecar in each half, never the half's first, and the
cell in it: every seed then costs the bisection the same count of checks
(27 at 128 sidecars).

The window is a FIXED count of blocks (`blocks_prepared`), so every run
of a commit measures the same blocks. Set-up has the service compile the
configuration's `warmup_keys` and sends one bisecting and one valid block
of another draw, so the window's blocks are new to the service (which
keeps nothing from one flush to the next in any case).
"""

from __future__ import annotations

import sys
import time

import numpy as np

from benchmark.reference import das_ref as ref


class Traffic:
    def __init__(self, config: dict, params: dict, seed: int):
        self.per_block = int(config["blobs_per_block"])
        self.columns = int(config["columns_per_block"])
        self.warmup_keys = [tuple(key) for key in config.get("warmup_keys", [])]
        self.pool_size = int(params["blob_pool"])
        self.invalid_first = int(params["invalid_first"])
        self.prepared = int(params["blocks_prepared"])  # the window's blocks: run.py stops there
        self.timeout = float(params.get("timeout_s", 600))
        self.seed = seed
        self.answers: list[tuple[int, tuple]] = []
        self.submit_ms: list[float] = []  # a block's submits, first to last

    # ------------------------------------------------------------ inputs --

    def make_inputs(self) -> None:
        rng = np.random.default_rng([self.seed, 0xDA5])
        self.pool = [ref.extend_and_prove(ref.random_blob(rng)) for _ in range(self.pool_size)]

    def block(self, number: int, warmup: bool = False) -> list[tuple]:
        """Block `number` of the window as its sidecars `(index, column,
        kzg_commitments, kzg_proofs)`; with `warmup` another draw under
        the same number."""
        rng = np.random.default_rng([self.seed, 0xDA6, number, int(warmup)])
        blobs = [self.pool[int(i)] for i in rng.permutation(self.pool_size)[: self.per_block]]
        order = rng.permutation(ref.NUMBER_OF_COLUMNS)[: self.columns]
        commitments = tuple(commitment for commitment, _, _ in blobs)
        sidecars = [
            (
                int(col),
                tuple(cells[col] for _, cells, _ in blobs),
                commitments,
                tuple(proofs[col] for _, _, proofs in blobs),
            )
            for col in order
        ]
        if number == self.invalid_first:
            for at, row in self.wrong_places(number):
                col, column, _, proofs = sidecars[at]
                other = blobs[row][2][(col + 1) % ref.NUMBER_OF_COLUMNS]
                sidecars[at] = (col, column, commitments,
                                proofs[:row] + (other,) + proofs[row + 1 :])
        return sidecars

    def wrong_places(self, number: int) -> list[tuple[int, int]]:
        """(sidecar, cell) of one wrong proof in each half of the flush,
        from the seed, the sidecar never a half's first."""
        half = self.columns // 2
        rng = np.random.default_rng([self.seed, 0xDA7, number])
        first = int(rng.integers(1, half)) if half > 1 else 0
        second = half + (int(rng.integers(1, self.columns - half)) if self.columns - half > 1 else 0)
        places = [first] if second == first else [first, second]
        return [(at, int(rng.integers(0, self.per_block))) for at in places]

    # ------------------------------------------------------------ driving --

    def setup(self, svc) -> None:
        if not hasattr(svc, "submit_column_verify"):  # before any input is made: at once
            raise RuntimeError("the program has no submit_column_verify")
        self.make_inputs()
        # the deployment's warm-up list (the configuration's file): a flush
        # whose buckets nobody compiled goes through the host
        if svc.precompile(self.warmup_keys) != len(self.warmup_keys):
            raise RuntimeError(f"the service did not compile {self.warmup_keys}")
        # a block that bisects and a valid one: both paths and the host's
        # tables are warm before the window
        self._submit(svc, self.block(self.invalid_first, warmup=True))
        self._submit(svc, self.block(self.invalid_first + 1, warmup=True))
        self.submit_ms.clear()
        was = svc.stats()["flushes"]
        self.flushes = lambda: {k: v - was[k] for k, v in svc.stats()["flushes"].items()}

    def _submit(self, svc, sidecars) -> tuple:
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        with TraceAnnotation("client.submit"):
            futs = [svc.submit_column_verify(s) for s in sidecars]
        self.submit_ms.append((time.perf_counter() - t0) * 1e3)
        with TraceAnnotation("client.wait_verdict"):
            return tuple(bool(f.result(timeout=self.timeout)) for f in futs)

    def request(self, svc, i: int) -> None:
        self.answers.append((i, self._submit(svc, self.block(i))))

    def release(self) -> None:
        pass

    def compare(self, control: bool = False) -> dict:
        """Every verdict of the window against the reference's for the same
        sidecar; the control's verdicts (every well-formed sidecar
        accepted) in the program's place where asked."""
        # whether a block's submits fit the batcher's deadline decides how
        # many flushes, and so executions, a block costs: said beside the result
        took = sorted(self.submit_ms) or [0.0]
        print(f"a block's submits: median {took[len(took) // 2]:.3f} ms, "
              f"longest {took[-1]:.3f} ms; flushes of the window's {len(took)} blocks, by reason: {self.flushes()}",
              file=sys.stderr)
        judge = ref.Judge()
        wrong = 0
        for number, verdicts in self.answers:
            for sidecar, verdict in zip(self.block(number), verdicts):
                want = judge.verify_sidecar(sidecar)
                if control:
                    verdict = judge.accept_without_check(sidecar)
                wrong += verdict != want
        return {"verdicts_wrong": (wrong, 0)}
