"""Closed loop of whole mainnet-shaped slots through `submit_slot`, after a
cold boot of the slot world and one untimed warm-up slot.

A copy of chip_smoke.py's generators (`host_world`, `build_slots`), with
every item valid and no epoch boundary: `committees` attestations of
`committee_size` members at `participation`, a `sync_size`-key sync
aggregate, `blobs` full-size sidecars. `slots_prepared` slots are made at
set-up (slots cannot be replayed: the slot number is the dedup key); the
window closes early when they are used up.

NOT YET A CELL. Two things here still lean on the program, and a PR that
adds the cell has to settle them (PERF.md, Open questions): the requests are
signed with the program's own BLS (`utils/bls`), and the answers are compared
with the program's own sequential host fold (`ops/slot_pipeline.host_slot_fold`),
not with a reference kept here.
"""

from __future__ import annotations

import dataclasses
import hashlib

import numpy as np

from benchmark.reference import kzg_ref


def _digest(*parts) -> bytes:
    return hashlib.sha256(repr(parts).encode()).digest()


class Traffic:
    def __init__(self, config: dict, params: dict, seed: int):
        self.n = int(config["validators"])
        self.params = params
        self.prepared = int(params["slots_prepared"])
        self.timeout = float(params.get("timeout_s", 1100))
        self.seed = seed
        self.answers: list[tuple[int, object]] = []

    def make_requests(self) -> list:
        """`slots_prepared` + 1 consecutive slot requests from the seed. Each
        validator sits in at most one committee; an aggregate signature is ONE
        sign under the sum of the members' secret keys."""
        from eth_consensus_specs_tpu.crypto.fields import R
        from eth_consensus_specs_tpu.ops.slot_pipeline import SlotAttestation, SlotRequest
        from eth_consensus_specs_tpu.utils import bls

        p, n = self.params, self.n
        count = int(p["slots_prepared"]) + 1
        committees, size = int(p["committees"]), int(p["committee_size"])
        if count * committees * size > n:
            raise ValueError("registry too small for disjoint committees")
        rng = np.random.default_rng([self.seed, 0x5107])
        duty = rng.permutation(n)[: count * committees * size].reshape(count, committees, size)
        base = 1_000_003 + (self.seed << 24)

        def signed(members, message: bytes):
            pubkeys = tuple(bytes(bls.SkToPk(base + int(v))) for v in members)
            return pubkeys, bytes(bls.Sign(sum(base + int(v) for v in members) % R, message))

        blobs = [kzg_ref.random_sidecar(rng) for _ in range(int(p["blobs"]))]
        reqs = []
        for slot in range(count):
            atts = []
            for c in range(committees):
                committee = tuple(int(v) for v in duty[slot, c])
                bits = rng.random(size) < float(p["participation"])
                bits[0] = True
                root = _digest("attestation", self.seed, slot, c)
                pubkeys, sig = signed([v for v, b in zip(committee, bits) if b], root)
                atts.append(SlotAttestation(
                    subnet=0, root=root, committee=committee,
                    bits=tuple(int(b) for b in bits), pubkeys=pubkeys, sig=sig,
                ))
            sync = [int(v) for v in rng.choice(n, int(p["sync_size"]), replace=False)]
            sync_msg = _digest("sync", self.seed, slot)
            sync_pubkeys, sync_sig = signed(sync, sync_msg)
            reqs.append(SlotRequest(
                slot=slot, attestations=tuple(atts), sync_pubkeys=sync_pubkeys,
                sync_message=sync_msg, sync_sig=sync_sig, sync_indices=tuple(sync),
                blobs=tuple(blobs), epoch_boundary=False,
            ))
        return reqs

    def setup(self, svc) -> None:
        self.requests = self.make_requests()
        svc.slot_world().boot()  # cold ingest, forest built on the device, prewarm
        self.warmup = self._submit(svc, self.requests[0])

    def _submit(self, svc, req):
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("client.submit"):
            fut = svc.submit_slot(req)
        with TraceAnnotation("client.wait_verdict"):
            return fut.result(timeout=self.timeout)

    def request(self, svc, i: int) -> None:
        self.answers.append((i + 1, self._submit(svc, self.requests[i + 1])))

    def release(self) -> None:
        pass

    def compare(self, control: bool = False) -> dict:
        """Every slot of the window (and the warm-up slot before them, which
        the fold has to pass through) against the program's sequential host
        fold over the slot world's deterministic recipe."""
        import jax

        import __graft_entry__ as graft
        from eth_consensus_specs_tpu.ops.slot_pipeline import host_slot_fold, slot_spec
        from eth_consensus_specs_tpu.ops.state_root import synthetic_static

        spec = slot_spec()
        static = synthetic_static(spec, self.n)
        cols, just = graft._example_altair_inputs(self.n)
        cols, just = jax.device_put(cols), jax.device_put(just)
        wrong, epoch = 0, 0
        for number, got in [(0, self.warmup)] + self.answers:
            want, cols, just = host_slot_fold(spec, static, cols, just, self.requests[number], epoch)
            epoch = want.epoch
            wrong += sum(
                getattr(got, f.name) != getattr(want, f.name) for f in dataclasses.fields(want)
            )
            wrong += not (got.sync_verdict and all(got.att_verdicts) and all(got.blob_verdicts))
        return {"slot_fields_wrong": (wrong, 0)}
