"""Closed loop of mainnet blocks' attestations: a block's aggregates
submitted together through `submit_bls_aggregate`, the next block when all
verdicts are back.

The registry (keys by the public recipe of benchmark/reference/bls_ref.py,
nothing of the program) is shuffled by the seed into `slots` slots of
disjoint committees. Block `b` carries the committees of slots `b` and
`b + 1`, so half a block's keys are new each block and the registry cycles
in `slots` blocks. Each member signs with probability `participation`, the
first always; a committee's message is sha256 of (seed, draw, slot,
committee); its aggregate signature is (sum of the signers' secret keys) *
H(message). Block `invalid_first` of the window, and no other, carries one
wrong aggregate in EACH half of its flush, the place from the seed and
never a half's first (every seed then costs the bisection the same count
of checks): in the first half the signature of the other wrong place's
aggregate (a well-formed point that signs something else), in the second
a signer's key left out (the signature is right for a set
one larger: the committee sum has to notice). Without them every right
answer is True and a verifier that checks nothing compares as correct.

The window is a FIXED count of blocks (`blocks_prepared`), so every run
of a commit measures the same blocks. A block's aggregates name their
signers by registry index. Set-up registers the registry, has the
service compile the configuration's `warmup_keys` and sends one bisecting
and one valid block of another draw (other signers, other messages), so
the window's blocks are new to the service.
"""

from __future__ import annotations

import hashlib
import sys
import time

import numpy as np

from benchmark.reference import bls_ref as ref


class Traffic:
    def __init__(self, config: dict, params: dict, seed: int):
        self.validators = int(config["validators"])
        self.size = int(config["committee_size"])
        self.per_block = int(config["aggregates_per_block"])
        self.committees = max(self.per_block // 2, 1)  # a slot's; a block carries two slots
        self.spans = self.per_block // self.committees  # (one, where a block is one aggregate)
        self.slots = self.validators // (self.committees * self.size)
        self.warmup_keys = [tuple(key) for key in config.get("warmup_keys", [])]
        self.participation = float(params["participation"])
        self.invalid_first = int(params["invalid_first"])
        self.prepared = int(params["blocks_prepared"])  # the window's blocks: run.py stops there
        self.timeout = float(params.get("timeout_s", 600))
        self.seed = seed
        self.answers: list[tuple[int, tuple]] = []
        self.submit_ms: list[float] = []  # a block's submits, first to last

    # ------------------------------------------------------------ inputs --

    def make_inputs(self) -> None:
        self.known = ref.KnownKeys(self.seed, self.validators)
        self.duty = np.random.default_rng([self.seed, 0xA77]).permutation(self.validators)[
            : self.slots * self.committees * self.size
        ].reshape(self.slots, self.committees, self.size)
        self.signed: dict[tuple, tuple] = {}
        for number in range(self.prepared):
            self.block(number)
        for number in (self.invalid_first, self.invalid_first + 1):
            self.block(number, warmup=True)

    def aggregate(self, draw: int, slot: int, committee: int) -> tuple:
        """(signers' registry indices, message, aggregate signature) of one
        committee, signed once and kept."""
        key = (draw, slot, committee)
        if key not in self.signed:
            rng = np.random.default_rng([self.seed, 0xA78, draw, slot, committee])
            bits = rng.random(self.size) < self.participation
            bits[0] = True
            signers = self.duty[slot, committee][bits].astype(np.int32)
            message = hashlib.sha256(
                f"atts {self.seed} {draw} {slot} {committee}".encode()
            ).digest()
            self.signed[key] = (
                signers, message, ref.sign(self.known.secret_sum(signers), message))
        return self.signed[key]

    def block(self, number: int, warmup: bool = False) -> list[tuple]:
        """Block `number` of the window; with `warmup` the same slots under
        another draw."""
        items = [
            self.aggregate(int(warmup), (number + s) % self.slots, c)
            for s in range(self.spans) for c in range(self.committees)
        ]
        if number == self.invalid_first:
            first, second = self.wrong_places(number)
            signers, message, _ = items[first]
            other = self.aggregate(int(warmup), (number + self.spans) % self.slots, 0)
            items[first] = (signers, message, (items[second] if self.spans > 1 else other)[2])
            if self.spans > 1:
                signers, message, signature = items[second]
                items[second] = (signers[:-1], message, signature)
        return items

    def wrong_places(self, number: int) -> tuple[int, int]:
        """One place in each half of the flush, from the seed, not a half's first."""
        half = self.committees
        rng = np.random.default_rng([self.seed, 0xA79, number])
        first = int(rng.integers(1, half)) if half > 1 else 0
        second = half + (int(rng.integers(1, half)) if half > 1 else 0)
        return first, second

    # ------------------------------------------------------------ driving --

    def setup(self, svc) -> None:
        register = svc.register_pubkeys  # a program without the verb fails here, at once
        self.make_inputs()
        register(self.known.pubkeys)
        # the deployment's warm-up list (the configuration's file): the
        # committee sums of a bucket nobody compiled go through the C core
        if svc.precompile(self.warmup_keys) != len(self.warmup_keys):
            raise RuntimeError(f"the service did not compile {self.warmup_keys}")
        # a block that bisects and a valid one: the key table's limbs and
        # the host's tables are warm before the window
        self._submit(svc, self.block(self.invalid_first, warmup=True))
        self._submit(svc, self.block(self.invalid_first + 1, warmup=True))
        self.submit_ms.clear()
        was = svc.stats()["flushes"]
        self.flushes = lambda: {k: v - was[k] for k, v in svc.stats()["flushes"].items()}

    def _submit(self, svc, items) -> tuple:
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        with TraceAnnotation("client.submit"):
            futs = [svc.submit_bls_aggregate(s, m, sig) for s, m, sig in items]
        self.submit_ms.append((time.perf_counter() - t0) * 1e3)
        with TraceAnnotation("client.wait_verdict"):
            return tuple(bool(f.result(timeout=self.timeout)) for f in futs)

    def request(self, svc, i: int) -> None:
        self.answers.append((i, self._submit(svc, self.block(i))))

    def release(self) -> None:
        pass

    def compare(self, control: bool = False) -> dict:
        """Every verdict of the window against the reference's for the same
        aggregate; the control's verdicts (every well-formed aggregate
        accepted) in the program's place where asked."""
        judge = ref.accept_well_formed if control else None
        # whether a block's submits fit the batcher's deadline decides how
        # many flushes, and so pairings, a block costs: said beside the result
        took = sorted(self.submit_ms) or [0.0]
        print(f"a block's submits: median {took[len(took) // 2]:.3f} ms, "
              f"longest {took[-1]:.3f} ms; flushes of the window's {len(took)} blocks, by reason: {self.flushes()}",
              file=sys.stderr)
        wrong = 0
        for number, verdicts in self.answers:
            for (signers, message, signature), verdict in zip(self.block(number), verdicts):
                pubkeys = [self.known.pubkeys[v] for v in signers]
                want = ref.fast_aggregate_verify(self.known, pubkeys, message, signature)
                if judge is not None:
                    verdict = judge(self.known, pubkeys, message, signature)
                wrong += verdict != want
        return {"verdicts_wrong": (wrong, 0)}
