"""Closed loop of Deneb blocks: a block's full-size sidecars submitted
together through `submit_blob_verify`, the next block when all verdicts are
back.

Set-up makes a pool of valid (blob, commitment, proof) sidecars from the
seed by the testing setup's public trapdoor (benchmark/reference/kzg_ref.py:
milliseconds a blob, nothing of the program). A block draws `blobs_per_block`
of them by a permutation seeded with the block's number. Block
`invalid_first` (and, where the traffic file gives `invalid_every`, every
so-many-th after it) carries a wrong proof in EACH half of the flush:
another blob's proof, a well-formed point that opens nothing. Without such
sidecars every right answer is True, and a verifier that opens no proof, or
none in one half of a flush, would compare as correct. The seed draws the
place in each half, never the half's first: the program's bisection then
makes the same number of checks for every seed (11 at six sidecars), so
every seed is the same work.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import kzg_ref as ref


class Traffic:
    def __init__(self, config: dict, params: dict, seed: int):
        self.per_block = int(config["blobs_per_block"])
        self.pool_size = int(params["pool_blobs"])
        self.invalid_first = int(params["invalid_first"])
        self.invalid_every = int(params.get("invalid_every", 0))  # 0: that one block alone
        self.timeout = float(params.get("timeout_s", 600))
        self.seed = seed
        self.answers: list[tuple[int, tuple]] = []

    def make_inputs(self) -> None:
        rng = np.random.default_rng([self.seed, 0xB10B])
        self.pool = [ref.random_sidecar(rng) for _ in range(self.pool_size)]

    def block(self, number: int, warmup: bool = False) -> list[tuple[bytes, bytes, bytes]]:
        """Block `number` of the window; with `warmup` another draw under the
        same number, so that the window's blocks are new to the service."""
        picks = np.random.default_rng(
            [self.seed, 0xB10C, number, int(warmup)]
        ).permutation(self.pool_size)
        sidecars = [self.pool[int(i)] for i in picks[: self.per_block]]
        if self.carries_wrong_proofs(number):
            # the proofs of two sidecars of the pool that are not in this block
            for k, at in enumerate(self.wrong_places(number)):
                blob, commitment, _ = sidecars[at]
                sidecars[at] = (blob, commitment, self.pool[int(picks[self.per_block + k])][2])
        return sidecars

    def carries_wrong_proofs(self, number: int) -> bool:
        since = number - self.invalid_first
        return since == 0 or (since > 0 and self.invalid_every > 0 and since % self.invalid_every == 0)

    def wrong_places(self, number: int) -> tuple[int, int]:
        """One place in each half of the flush, from the seed, not a half's first."""
        half = self.per_block // 2
        rng = np.random.default_rng([self.seed, 0xB10D, number])
        first = int(rng.integers(1, half)) if half > 1 else 0
        second = half + (int(rng.integers(1, self.per_block - half)) if self.per_block - half > 1 else 0)
        return first, second

    def setup(self, svc) -> None:
        self.make_inputs()
        # a block that bisects and a valid one: both paths' programs and the
        # host's tables are warm before the window
        self._submit(svc, self.block(self.invalid_first, warmup=True))
        self._submit(svc, self.block(self.invalid_first + 1, warmup=True))

    def _submit(self, svc, sidecars) -> tuple:
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("client.submit"):
            futs = [svc.submit_blob_verify(*s) for s in sidecars]
        with TraceAnnotation("client.wait_verdict"):
            return tuple(bool(f.result(timeout=self.timeout)) for f in futs)

    def request(self, svc, i: int) -> None:
        self.answers.append((i, self._submit(svc, self.block(i))))

    def release(self) -> None:
        pass

    def compare(self, control: bool = False) -> dict:
        """Every verdict of the window against the reference's for the same
        sidecar; the control's verdicts (no proof opened) in the program's
        place where asked."""
        memo: dict[tuple, tuple[bool, bool]] = {}
        wrong = 0
        for number, verdicts in self.answers:
            for sidecar, verdict in zip(self.block(number), verdicts):
                key = (sidecar[1], sidecar[2])
                if key not in memo:
                    memo[key] = (ref.verify_blob(*sidecar), ref.accept_without_proof(*sidecar))
                want, lazy = memo[key]
                wrong += (lazy if control else verdict) != want
        return {"verdicts_wrong": (wrong, 0)}
