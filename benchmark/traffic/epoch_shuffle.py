"""Closed loop of epochs' committee lists through `submit_committees`: the
next epoch when the last one's list is back.

Set-up makes a pool of `epoch_pool` epochs from the seed, each its own
32-byte shuffling seed and its own active set: a sorted draw of `n_k`
registry indices out of the configuration's `registry`. The active count
walks as mainnet's does, `n_k = n_(k-1) + d_k` from `first_active`, `d_k` a
non-zero draw from [-churn_per_epoch, churn_per_epoch], drawn again where
the count would land on a whole chunk (a multiple of 256): every count sits
under the configuration's lane bucket with a part-filled last chunk, and
consecutive requests differ in BOTH seed and count, so a program compiled
for a count, or one that treats the count as its bucket, shows. The window
cycles the pool in a seeded order; no two consecutive requests are equal.
Set-up has the service compile the configuration's `warmup_keys` and sends
every pool epoch once.

A request and its answer are 4 bytes a validator (int32, 4 MB at 2**20)
and a window holds thousands, so the client keeps ONE copy of each distinct
answer an epoch and notes which copy a request got: one array comparison a
request, after the answer is back and under its own annotation
(`client.keep_answer`), its mean printed to standard error after the window
so that a reader can take it off `verdict_ms`. After the window every
distinct answer is compared with the reference's whole list, and so every
request's.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from benchmark.reference import shuffle_ref as ref

SAMPLED_POSITIONS = 64  # an epoch: these go through the spec's per-index form too


class Traffic:
    def __init__(self, config: dict, params: dict, seed: int):
        self.bucket = int(config["validators"])
        self.registry = int(config["registry"])
        self.warmup_keys = [tuple(key) for key in config.get("warmup_keys", [])]
        self.pool_size = int(params["epoch_pool"])
        self.first_active = int(params["first_active"])
        self.churn = int(params["churn_per_epoch"])
        self.timeout = float(params.get("timeout_s", 600))
        self.seed = seed
        self.keep_seconds = 0.0  # the client's own comparisons, inside the requests' time
        self.answers: list[tuple[int, int]] = []  # (pool epoch, which of its distinct answers)
        self.distinct: dict[int, list[np.ndarray]] = {}

    # ------------------------------------------------------------ inputs --

    def make_inputs(self) -> None:
        rng = np.random.default_rng([self.seed, 0x5AFF1E])
        self.epochs: list[tuple[np.ndarray, bytes]] = []
        count = self.first_active
        for k in range(self.pool_size):
            if k:
                count = self._next_count(rng, count)
            if not 1 <= count <= self.bucket or count % 256 == 0:
                raise ValueError(f"active count {count}: not under the lane bucket {self.bucket} "
                                 "with a part-filled last chunk")
            active = np.sort(rng.choice(self.registry, count, replace=False)).astype(np.int32)
            self.epochs.append((active, rng.bytes(32)))
        self.order = [int(i) for i in rng.permutation(self.pool_size)]

    def _next_count(self, rng, count: int) -> int:
        """The next epoch's active count: a non-zero step of at most the
        churn, drawn again where it would land on a whole chunk."""
        while True:
            step = int(rng.integers(-self.churn, self.churn + 1))
            if step and (count + step) % 256:
                return count + step

    # ------------------------------------------------------------ driving --

    def setup(self, svc) -> None:
        if not hasattr(svc, "submit_committees"):  # before any input is made: at once
            raise RuntimeError("the program has no submit_committees")
        self.make_inputs()
        # the deployment's warm-up list (the configuration's file): a request
        # whose lane bucket nobody compiled goes through the host
        if svc.precompile(self.warmup_keys) != len(self.warmup_keys):
            raise RuntimeError(f"the service did not compile {self.warmup_keys}")
        for k in range(self.pool_size):
            self._submit(svc, k)

    def _submit(self, svc, k: int) -> np.ndarray:
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("client.submit"):
            fut = svc.submit_committees(*self.epochs[k])
        with TraceAnnotation("client.wait_verdict"):
            return np.asarray(fut.result(timeout=self.timeout))

    def request(self, svc, i: int) -> None:
        from jax.profiler import TraceAnnotation

        k = self.order[i % self.pool_size]
        answer = self._submit(svc, k)
        with TraceAnnotation("client.keep_answer"):
            t0 = time.perf_counter()
            seen = self.distinct.setdefault(k, [])
            which = next((j for j, kept in enumerate(seen) if np.array_equal(kept, answer)),
                         len(seen))
            if which == len(seen):
                seen.append(answer)
            self.answers.append((k, which))
            self.keep_seconds += time.perf_counter() - t0

    def release(self) -> None:
        pass

    # ------------------------------------------------------------ correct --

    def is_wrong(self, k: int, answer: np.ndarray, want: np.ndarray) -> bool:
        """Whether an answer to pool epoch `k` differs from the reference's
        whole list anywhere or in length, or, at the epoch's sampled
        positions, from the spec's per-index form."""
        active, seed = self.epochs[k]
        if answer.shape != want.shape or not np.array_equal(answer, want):
            return True
        rng = np.random.default_rng([self.seed, 0x5AFF1F, k])
        return any(
            int(answer[i]) != int(active[ref.compute_shuffled_index(int(i), len(active), seed)])
            for i in rng.integers(0, len(active), SAMPLED_POSITIONS)
        )

    def compare(self, control: bool = False) -> dict:
        """Every answer of the window against the reference's whole list for
        its epoch. The control (STALE) puts in the program's place the list
        of the pool epoch served just before, cut or padded to length: a
        committee table kept from the last epoch because the set hardly
        moved."""
        if self.answers and not control:
            print(f"client.keep_answer: {1e3 * self.keep_seconds / len(self.answers):.4f} ms a "
                  f"request over {len(self.answers)} (inside verdict_ms)", file=sys.stderr)
        used = sorted({k for k, _ in self.answers})
        before = {k: self.order[self.order.index(k) - 1] for k in used}
        lists = {k: ref.shuffled_list(*self.epochs[k])
                 for k in set(used) | (set(before.values()) if control else set())}
        wrong = 0
        for k in used:
            want = lists[k]
            answers = [np.resize(lists[before[k]], want.shape)] if control else self.distinct[k]
            verdicts = [self.is_wrong(k, answer, want) for answer in answers]
            wrong += sum(verdicts[0 if control else which] for e, which in self.answers if e == k)
        return {"lists_wrong": (wrong, 0)}
