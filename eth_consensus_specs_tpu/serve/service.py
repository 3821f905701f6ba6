"""The in-process async verification service.

Eight submit verbs return ``concurrent.futures.Future``s:

  * ``submit_bls_aggregate(pubkeys, message, signature) -> Future[bool]``
    (the signers as 48-byte keys, or as indices into the registry that
    ``register_pubkeys`` handed over once: decoded and validated once,
    kept on the host and, as limbs, on the device — ops/key_table.py)
  * ``submit_aggregate(signatures) -> Future[bytes]`` (96-byte
    aggregate signature — the aggregation-pipeline op: ragged
    committees batch into ONE G2 many-sum dispatch per flush)
  * ``submit_blob_verify(blob, commitment, proof) -> Future[bool]``
    (the DAS workload op: the flush folds into ONE batched inverse FFT
    + ONE RLC multi-MSM + one pairing — ops/kzg_batch)
  * ``submit_column_verify(sidecar) -> Future[bool]`` (a Fulu data
    column sidecar ``(index, column, kzg_commitments, kzg_proofs)``: the
    flush's cells ride ONE batched inverse FFT of 64 points and ONE
    multi-MSM whose items are the sidecars, a reject is isolated from
    the partial sums with no second execution — ops/das_batch)
  * ``submit_hash_tree_root(chunks) -> Future[bytes]`` (32-byte root)
  * ``submit_state_root(arrays, meta, balances, eff_bal, inact, just)
    -> Future[np.ndarray]`` (u32[8] root words)
  * ``submit_committees(active_indices, seed) -> Future[np.ndarray]``
    (an epoch's shuffled committee list, int32[n]:
    ``active_indices[compute_shuffled_index(i, n, seed)]`` for every i,
    ONE execution of a program compiled a lane bucket with the active
    count a traced number — ops/shuffle)
  * ``submit_slot(SlotRequest) -> Future[SlotResult]`` (the whole-slot
    state-transition pipeline: verify → aggregate → column updates →
    incremental re-root against this service's resident slot world —
    serve/slot.py owns the state, ops/slot_pipeline.py the legs; the
    result is bit-identical to the sequential host fold)

Pipeline: ``submit`` → admission (typed ``Overloaded`` shed past the
queue/byte caps) → micro-batcher (flush on size / deadline / pressure)
→ **batch thread** (host prep: SSZ chunk packing, pubkey decode — runs
while the previous flush executes) → bounded hand-off queue (depth 2:
the pipeline's backpressure seam) → **dispatch thread** (device
execution, bucket-padded; whole-batch degradation to host oracles
through ``fault.degrade("serve.dispatch", ...)`` on device death).

Result parity is a hard invariant: every future resolves to exactly
what the direct per-request ops call returns (tests/test_serve.py
hammers this with concurrent submitters), on both the device path and
the degraded host path.

Counters/events: ``serve.requests``, ``serve.flushes``,
``serve.flush.{size,deadline,pressure,idle,close}``, ``serve.batch_items``,
``serve.compiles`` (each first dispatch's wall time lands in the
``serve.compile_ms`` histogram — count stays in lockstep with the
counter, ``stats()`` and serve_bench report its p50/p99),
``serve.rejected[.reason]``, gauges
``serve.queue_depth`` / ``serve.in_flight_bytes``, a ``serve.flush``
event per flush (batch size, reason, in-flush wait p50/p99) and a
``serve.stats`` event at close with run-level p50/p99 wait.

Latency accounting: every request's batcher wait lands in the
**mergeable log-bucket histogram** ``serve.wait_ms`` (obs/histogram.py)
— run-level p50/p99 come from bucket quantiles over the WHOLE run (no
reservoir truncation, no sort-under-lock), per-flush p50/p99 from a
throwaway per-flush histogram, and gen-pool workers' wait
distributions merge into the parent registry bucket-by-bucket.

Tracing: ``submit_*`` captures a trace context (child of the caller's
active context, or a fresh root) into the Request; the flush event
links its members' wire ids under ``flows`` and the ``serve.dispatch``
span runs under its own context carrying the same flow links — the
Perfetto flow-event idiom across the submit→batch→dispatch thread
hand-offs.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from queue import Queue

import numpy as np

from eth_consensus_specs_tpu import fault, obs
from eth_consensus_specs_tpu.analysis import lockwatch
from eth_consensus_specs_tpu.obs import trace, waterfall, xprof
from eth_consensus_specs_tpu.obs.histogram import Histogram
from eth_consensus_specs_tpu.parallel import mesh_ops

from . import buckets
from .admission import AdmissionController, Overloaded  # noqa: F401  (re-export)
from .batcher import MicroBatcher, Request
from .config import ServeConfig

# marks the service's own worker threads so routed entry points
# (utils/bls.FastAggregateVerify) never re-submit from inside a dispatch
# — that would deadlock the single dispatch thread on its own future
_SERVICE_TLS = threading.local()


def on_service_thread() -> bool:
    return getattr(_SERVICE_TLS, "active", False)


class VerifyService:
    def __init__(self, config: ServeConfig | None = None, name: str = "serve"):
        self.config = config or ServeConfig.from_env()
        self.name = name
        # every kernel this service dispatches compiles through the
        # persistent cache (utils/cache.py: JAX_COMPILATION_CACHE_DIR or
        # <checkout>/.jax_cache) — without it a service on an accelerator
        # pays minutes of limb-kernel compiles in every process
        from eth_consensus_specs_tpu.utils.cache import enable_persistent_cache

        enable_persistent_cache()
        # what XLA compiles from here on is filed under the leg it
        # compiled in (xla.compile_ms.<leg>, the xla.compile event)
        xprof.install_compile_listener()
        self.admission = AdmissionController(self.config.max_queue, self.config.max_bytes)
        self._batcher = MicroBatcher()
        # depth-2 hand-off: batch N+1's host prep overlaps batch N's
        # device execution; a third flush blocks the batch thread, which
        # lets the queue grow and admission shed — backpressure, not RAM
        self._dispatch_q: Queue = Queue(maxsize=2)
        self._closed = False
        self._close_lock = lockwatch.wrap(
            threading.Lock(), "serve.service.VerifyService._close_lock"
        )
        # run-level wait distribution: a mergeable log-bucket histogram
        # (every wait of the whole run, O(1) record, quantiles from
        # buckets — the old 4096-sample deque truncated history under
        # load and had to sort under a lock to answer p99)
        self._waits = Histogram()
        self._dispatch_busy = False
        # the slot world is lazy: first submit_slot (or slot_world())
        # pays boot + prewarm; None until then so slot-free services
        # never build a registry
        self._slot_world = None
        self._slot_world_lock = threading.Lock()
        # the registry's decoded public keys (ops/key_table.KeyTable): None
        # until register_pubkeys, and then BLS requests resolve against it
        self._keys = None
        self._batch_thread = threading.Thread(
            target=self._batch_loop, name=f"{name}-batch", daemon=True
        )
        self._dispatch_thread = threading.Thread(
            target=self._dispatch_loop, name=f"{name}-dispatch", daemon=True
        )
        self._batch_thread.start()
        self._dispatch_thread.start()

    # ------------------------------------------------------------ submit --

    def _submit(self, kind: str, payload: tuple, cost_bytes: int,
                canary: bool = False) -> Future:
        if self._closed:
            raise RuntimeError(f"service {self.name} is shut down")
        # the waterfall anchor: t_submit and the stamp vector share one
        # clock origin so the admit stage starts at zero, not at however
        # long admission held its lock
        t0 = time.monotonic()
        stamps: dict = {}
        if canary:
            # canary traffic class (obs/canary.py): exempt from admission
            # shed accounting — a canary occupying a queue slot could shed
            # a real request, which inverts the monitor/monitored roles
            waterfall.mark(stamps, "admitted", t0)
        else:
            self.admission.admit(cost_bytes, stamps)  # raises Overloaded past the caps
        # child of the caller's active trace (or a fresh root): the ids
        # ride the Request through the batch/dispatch thread hand-offs
        req = Request(kind=kind, payload=payload, cost_bytes=cost_bytes,
                      t_submit=t0, trace=trace.child(), stamps=stamps,
                      canary=canary)
        try:
            self._batcher.put(req)
        except RuntimeError:
            self._release_once(req)
            raise
        if canary:
            obs.count("canary.requests", 1)
        else:
            obs.count("serve.requests", 1)
            obs.count(f"serve.requests.{kind}", 1)
        return req.future

    def register_pubkeys(self, pubkeys: list) -> None:
        """Hand the registry's public keys over, in registry order, once:
        each is decompressed and KeyValidated here (ValueError names the
        first that fails, and nothing is kept), and from then on a BLS
        request's signers resolve to registry indices, given as indices or
        found by one dictionary lookup a key, and their committee sums run
        from the resident table. A key that is not in the registry is
        decoded as before."""
        from eth_consensus_specs_tpu.ops.key_table import KeyTable

        with obs.span("serve.register_pubkeys", keys=len(pubkeys)) as sp:
            self._keys = KeyTable(pubkeys)
        obs.observe("serve.setup_ms.register_pubkeys", sp.seconds * 1e3)

    def submit_bls_aggregate(self, pubkeys, message: bytes, signature: bytes,
                             canary: bool = False) -> Future:
        """FastAggregateVerify-shaped request; resolves to the exact bool
        ``ops.bls_batch.batch_verify_aggregates([item])`` returns.
        ``pubkeys`` is a list of 48-byte keys, or an integer array of
        indices into the registered registry (ValueError without one, or
        past its end): both forms give the same verdict."""
        if isinstance(pubkeys, np.ndarray):
            if pubkeys.dtype.kind not in "iu" or pubkeys.ndim != 1:
                raise ValueError("signers by index: a one-dimensional integer array")
            if self._keys is None or (pubkeys.size and self._keys.resolve(pubkeys) is None):
                raise ValueError("signers by index: not in the registered registry")
            pks = pubkeys
        else:
            pks = [bytes(p) for p in pubkeys]
        item = (pks, bytes(message), bytes(signature))
        cost = 48 * len(pks) + len(item[1]) + len(item[2])
        return self._submit("bls", item, cost, canary=canary)

    def submit_aggregate(self, signatures: list, canary: bool = False) -> Future:
        """Aggregate compressed G2 signatures (one committee's gossip
        contribution); resolves to the exact bytes
        ``crypto.signature.aggregate(signatures)`` returns — empty or
        malformed inputs resolve exceptionally with the same
        ValueError the direct call raises."""
        sigs = tuple(bytes(s) for s in signatures)
        return self._submit("agg", (sigs,), 96 * max(len(sigs), 1), canary=canary)

    def submit_blob_verify(
        self, blob: bytes, commitment: bytes, proof: bytes, canary: bool = False
    ) -> Future:
        """Blob KZG verification (the DAS workload op); resolves to the
        exact bool ``ops.kzg_batch.verify_blob_host`` returns —
        malformed inputs are ``False`` verdicts, never exceptions. The
        whole flush folds into ONE batched inverse FFT + ONE RLC
        multi-MSM + one pairing; invalid items isolate via bisection.
        Admission accounts the FULL blob payload (131 KiB each), so the
        byte cap — not the queue cap — is what sheds at blob scale."""
        item = (bytes(blob), bytes(commitment), bytes(proof))
        return self._submit("kzg", item, sum(len(b) for b in item), canary=canary)

    def submit_column_verify(self, sidecar, canary: bool = False) -> Future:
        """One data column sidecar ``(index, column, kzg_commitments,
        kzg_proofs)`` (PeerDAS, Fulu); resolves to the exact bool
        ``ops.das_batch.verify_column_host`` returns: the spec's
        ``verify_data_column_sidecar and
        verify_data_column_sidecar_kzg_proofs`` on that sidecar alone, a
        malformed one ``False``, never an exception. A block's sidecars in
        one flush share their commitments' decoding, one inverse FFT, one
        multi-MSM and, where all are valid, one pairing. Admission
        accounts the sidecar's bytes (45 KB at 21 blobs). The three
        sequences are copied here and their elements taken as they are
        (``bytes`` in practice; the flush's parse converts whatever else):
        a block's 128 submits have the batcher's 5 ms to close one flush,
        and take ~4 of them on a v5e's host."""
        index, column, commitments, proofs = sidecar
        parts = tuple(column), tuple(commitments), tuple(proofs)
        cost = 8 + sum(sum(map(len, part)) for part in parts)
        return self._submit("das", (int(index), *parts), cost, canary=canary)

    def submit_hash_tree_root(self, chunks: np.ndarray, canary: bool = False) -> Future:
        """Merkleize uint8[N, 32] chunks into the root of the pow2
        subtree holding them; resolves to the exact bytes
        ``ops.merkle.merkleize_subtree_device(chunks, depth)`` returns
        for depth = ceil(log2(N))."""
        chunks = np.ascontiguousarray(chunks)
        if chunks.ndim != 2 or chunks.shape[1] != 32 or chunks.dtype != np.uint8:
            raise ValueError("chunks must be uint8[N, 32]")
        depth = buckets.subtree_depth(chunks.shape[0])
        return self._submit("htr", (chunks, depth), int(chunks.nbytes),
                            canary=canary)

    def submit_state_root(
        self, arrays, meta, balances, effective_balance, inactivity_scores, just
    ) -> Future:
        """Post-accounting-epoch state root; resolves to the u32[8] root
        words ``ops.state_root.post_epoch_state_root`` returns."""
        cost = int(meta.n_validators) * 8 * 3  # the dynamic columns
        return self._submit(
            "state_root",
            (arrays, meta, balances, effective_balance, inactivity_scores, just),
            cost,
        )

    def submit_committees(self, active_indices: np.ndarray, seed: bytes) -> Future:
        """An epoch's committees as ONE list: resolves to int32[n]
        ``shuffled`` with ``shuffled[i] ==
        active_indices[compute_shuffled_index(i, n, seed)]`` for every i
        under the mainnet preset's 90 rounds, exact and from the request's
        own bytes alone; committee ``k`` of ``count`` is
        ``shuffled[n * k // count : n * (k + 1) // count]``
        (``compute_committee``). ``active_indices`` is a one-dimensional
        integer array of n >= 1 registry indices below 2**31, ``seed`` the
        caller's ``get_seed(state, epoch, DOMAIN_BEACON_ATTESTER)``
        (ValueError otherwise). The list is made on the device for a lane
        bucket ``precompile`` has compiled (``("shuffle", lanes)``), by the
        host's numpy form otherwise. Admission accounts the indices' 4
        bytes each."""
        if (not isinstance(active_indices, np.ndarray) or active_indices.ndim != 1
                or active_indices.dtype.kind not in "iu" or active_indices.size == 0):
            raise ValueError("active indices: a one-dimensional integer array, not empty")
        if int(active_indices.min()) < 0 or int(active_indices.max()) >= 1 << 31:
            raise ValueError("active indices: registry indices below 2**31")
        seed = bytes(seed)
        if len(seed) != 32:
            raise ValueError("seed: 32 bytes")
        active = active_indices.astype(np.int32)
        return self._submit("shuffle", (active, seed), int(active.nbytes))

    def submit_slot(self, req) -> Future:
        """One whole slot (ops/slot_pipeline.SlotRequest: attestations +
        sync aggregate + blob sidecars); resolves to the SlotResult the
        sequential host fold of the existing ops would produce —
        verdicts, per-subnet aggregates, and the post-slot state root,
        bit-identical. Stateful and idempotent: ``req.slot`` is the
        dedup key, a retried committed slot replays its recorded result.
        Admission accounts the full payload (blobs dominate)."""
        from eth_consensus_specs_tpu.ops.slot_pipeline import SlotRequest

        if not isinstance(req, SlotRequest):
            raise TypeError("submit_slot takes an ops.slot_pipeline.SlotRequest")
        cost = (
            sum(len(part) for b in req.blobs for part in b)
            + sum(96 + 48 * len(a.pubkeys) for a in req.attestations)
            + 48 * len(req.sync_pubkeys)
        )
        return self._submit("slot", req, max(cost, 1))

    def slot_world(self):
        """This service's slot-pipeline world (serve/slot.py), created
        from the config on first use. Public so replicas can boot it
        eagerly (restore + prewarm) before marking ready."""
        from .slot import SlotWorld

        with self._slot_world_lock:
            if self._slot_world is None:
                self._slot_world = SlotWorld(
                    n_validators=self.config.slot_validators,
                    ckpt_dir=self.config.slot_ckpt_dir,
                    dedup_cap=self.config.slot_dedup,
                )
            return self._slot_world

    # ------------------------------------------------------- batch thread --

    def _pressure(self) -> bool:
        return self.admission.depth() >= self.config.pressure_depth

    def _idle(self) -> bool:
        return self._dispatch_q.empty() and not self._dispatch_busy

    def _batch_loop(self) -> None:
        _SERVICE_TLS.active = True
        while True:
            # the batch thread's two states as spans, one a flush each: the
            # queue and prep stages time them already, the spans put them on
            # the profiler's clock, where a device-idle gap can be laid
            # under the batcher's deadline or under host prep
            with obs.span("serve.batch_wait"):
                flush = self._batcher.next_flush(
                    self.config.max_batch,
                    self.config.max_wait_s,
                    self._pressure,
                    self._idle if self.config.idle_flush else None,
                )
            if flush is None:
                break
            reqs, reason = flush
            now = time.monotonic()
            flush_hist = Histogram()  # per-flush quantiles, same buckets
            for r in reqs:
                waterfall.mark(r.stamps, "flush_assembled", now)
                wait_ms = (now - r.t_submit) * 1000.0
                if r.canary:
                    # canaries ride the flush but never the SLO metric:
                    # serve.wait_ms feeds the burn-rate windows and the
                    # wait-p99 objective (obs/canary.py)
                    obs.observe("canary.wait_ms", wait_ms)
                    continue
                flush_hist.record(wait_ms)
                self._waits.record(wait_ms)
                obs.observe("serve.wait_ms", wait_ms)
            obs.count("serve.flushes", 1)
            obs.count(f"serve.flush.{reason}", 1)
            obs.count("serve.batch_items", len(reqs))
            p50 = flush_hist.quantile(0.5)  # None for an all-canary flush
            p99 = flush_hist.quantile(0.99)
            obs.event(
                "serve.flush",
                reason=reason,
                batch_size=len(reqs),
                queue_depth=self.admission.depth(),
                wait_p50_ms=round(p50, 3) if p50 is not None else 0.0,
                wait_p99_ms=round(p99, 3) if p99 is not None else 0.0,
                # Perfetto-style flow links: each member request's wire
                # id, so a JSONL consumer can stitch submit-side traces
                # to this flush and its dispatch span
                flows=[trace.to_wire(r.trace) for r in reqs if r.trace],
            )
            with obs.span("serve.prep", batch=len(reqs)):
                self._prep(reqs)
            waterfall.mark_all(reqs, "prepped")
            self._dispatch_q.put(reqs)  # blocks at pipeline depth 2
            # stamped AFTER the put so the handoff stage bills the
            # depth-2 backpressure block, not the dispatch queue wait
            waterfall.mark_all(reqs, "dispatch_queued")
        self._dispatch_q.put(None)

    def _prep(self, reqs: list[Request]) -> None:
        """Host prep, overlapped with the previous flush's device work:
        SSZ chunk packing for htr, pubkey decompression warm-up for bls
        (where no registry was handed over: its keys are decoded already).
        A per-request prep failure resolves THAT future exceptionally and
        drops the request; co-batched requests are unaffected."""
        from eth_consensus_specs_tpu.crypto.signature import _load_sig
        from eth_consensus_specs_tpu.ops.merkle import _chunks_to_words

        if self._keys is None:
            from eth_consensus_specs_tpu.ops.bls_batch import warm_keys

            # warms the bounded decompression cache (a malformed key is
            # the flush's to refuse: nothing raises here)
            warm_keys([r.payload for r in reqs if r.kind == "bls"])
        das_reqs = [r for r in reqs if r.kind == "das"]
        if das_reqs:
            # the flush's sidecars together: structure checks, its distinct
            # commitments decoded once (a block's 128 sidecars carry the
            # same ones) and every proof in one call of the C core; None
            # marks a malformed sidecar (a False verdict, not an error)
            from eth_consensus_specs_tpu.ops.das_batch import prepare_columns

            for r, column in zip(das_reqs, prepare_columns([r.payload for r in das_reqs])):
                r.prepped = (column,)
        for r in reqs:
            try:
                if r.kind == "htr":
                    chunks, depth = r.payload
                    r.prepped = _chunks_to_words(chunks, 1 << depth)
                elif r.kind == "kzg":
                    # the heavy host-side parse (4096 field elements,
                    # point decompression, Fiat-Shamir challenge) runs
                    # here, overlapped with the previous flush's device
                    # work; None marks a malformed item (a False
                    # verdict, matching verify_blob_host — not an error)
                    from eth_consensus_specs_tpu.ops.kzg_batch import parse_item

                    r.prepped = (parse_item(r.payload),)
                elif r.kind == "slot":
                    # the whole-slot host prep: pubkey/signature
                    # decompression + blob parsing for every leg,
                    # overlapped with the previous flush's device work
                    from eth_consensus_specs_tpu.ops.slot_pipeline import prep_request

                    r.prepped = prep_request(r.payload)
                elif r.kind == "agg":
                    # G2 decompression is the per-signature fixed cost:
                    # pay it here, overlapped with the previous flush's
                    # device work. The error strings mirror
                    # crypto.signature.aggregate exactly — a rejected
                    # future carries what the direct call would raise.
                    if not r.payload[0]:
                        raise ValueError("cannot aggregate zero signatures")
                    pts = []
                    for s in r.payload[0]:
                        p = _load_sig(s)
                        if p is None:
                            raise ValueError("invalid signature in aggregate")
                        pts.append(p)
                    r.prepped = pts
            except Exception as exc:  # noqa: BLE001 — resolve, don't kill the thread
                self._resolve(r, exc=exc)

    # ---------------------------------------------------- dispatch thread --

    def _dispatch_loop(self) -> None:
        _SERVICE_TLS.active = True
        while True:
            reqs = self._dispatch_q.get()
            if reqs is None:
                break
            for r in reqs:
                if r.future.cancelled():
                    # cancelled while queued: nothing will resolve it, so
                    # its admission slot must be handed back here
                    self._release_once(r)
                    obs.count("serve.cancelled", 1)
            live = [r for r in reqs if not r.future.done()]
            if not live:
                continue
            t0 = time.monotonic()
            self._dispatch_busy = True
            waterfall.mark_all(live, "device_start")
            # every waterfall.leg this thread runs until close_flush adds
            # its milliseconds here; a degraded flush adds both attempts
            legs = waterfall.open_flush()
            try:
                # the dispatch span can't BELONG to the N requests it
                # serves, so it runs under its own context and LINKS
                # them: the flows attr carries each member's wire id
                with trace.activate(trace.child()):
                    with obs.span(
                        "serve.dispatch",
                        batch=len(live),
                        flows=",".join(
                            trace.to_wire(r.trace) for r in live if r.trace
                        ),
                    ):
                        results = fault.degrade(
                            "serve.dispatch",
                            lambda: self._execute(live, device=True),
                            lambda: self._execute(live, device=False),
                        )
            except BaseException as exc:  # noqa: BLE001 — futures carry the error
                for r in live:
                    self._resolve(r, exc=exc)
                continue
            finally:
                waterfall.close_flush()
                self._dispatch_busy = False
            waterfall.mark_all(live, "device_done")
            per_req_s = (time.monotonic() - t0) / len(live)
            for r in live:
                self._resolve(r, value=results[id(r)], service_s=per_req_s, legs=legs)

    def _execute(self, reqs: list[Request], device: bool) -> dict[int, object]:
        """Run one flush. ``device=True`` is the bucket-padded batched
        path (and the fault-injection site); ``device=False`` is the
        whole-batch host-oracle degradation — bit-identical results,
        no XLA anywhere."""
        if device:
            fault.check("serve.dispatch")
        mesh = mesh_ops.serve_mesh(self.config.mesh_chips or None) if device else None
        results: dict[int, object] = {}
        bls_reqs = [r for r in reqs if r.kind == "bls"]
        if bls_reqs:
            if device:
                from eth_consensus_specs_tpu.ops.bls_batch import verify_many

                # the device G1 MSM seam accounts its own compiles now
                # (bls_batch._rlc_pubkey_terms wraps the ONE batched
                # many-sum dispatch in first_dispatch, keyed by the
                # shared many_sum_shape bucket + mesh signature), so the
                # service just routes — mesh live shards the item axis
                verdicts = verify_many(
                    [r.payload for r in bls_reqs],
                    mesh=mesh if len(bls_reqs) >= mesh_ops.min_items() else None,
                    keys=self._keys,
                )
            else:
                from eth_consensus_specs_tpu.crypto.signature import fast_aggregate_verify

                # canaries stay out of the degraded_rate SLO numerator
                # (they are out of its serve.requests denominator too)
                obs.count("serve.degraded_items",
                          sum(1 for r in bls_reqs if not r.canary))
                verdicts = [
                    fast_aggregate_verify(self._signer_keys(r.payload[0]), *r.payload[1:])
                    for r in bls_reqs
                ]
            for r, v in zip(bls_reqs, verdicts):
                results[id(r)] = bool(v)

        kzg_reqs = [r for r in reqs if r.kind == "kzg"]
        if kzg_reqs:
            if device:
                from eth_consensus_specs_tpu.ops.kzg_batch import (
                    parse_item,
                    verify_many_blobs,
                )

                # _prep parsed each item off this thread (None in the
                # 1-tuple = malformed = a False verdict); the kzg seam
                # accounts its own compiles (fr_fft_key + kzg_msm_key
                # first_dispatch inside kzg_batch) and decides mesh
                # sharding by the live lane/row crossovers itself
                parsed = [
                    r.prepped[0] if r.prepped is not None else parse_item(r.payload)
                    for r in kzg_reqs
                ]
                verdicts = verify_many_blobs(
                    [r.payload for r in kzg_reqs], mesh=mesh, parsed=parsed
                )
            else:
                from eth_consensus_specs_tpu.ops.kzg_batch import verify_blob_host

                obs.count("serve.degraded_items",
                          sum(1 for r in kzg_reqs if not r.canary))
                verdicts = [verify_blob_host(*r.payload) for r in kzg_reqs]
            for r, v in zip(kzg_reqs, verdicts):
                results[id(r)] = bool(v)

        das_reqs = [r for r in reqs if r.kind == "das"]
        if das_reqs:
            from eth_consensus_specs_tpu.ops import das_batch

            if device:
                # _prep parsed the flush; the op accounts its own two
                # buckets and takes the device only for compiled ones
                verdicts = das_batch.verify_many_columns(
                    [r.payload for r in das_reqs],
                    parsed=[r.prepped[0] for r in das_reqs],
                )
            else:
                obs.count("serve.degraded_items",
                          sum(1 for r in das_reqs if not r.canary))
                verdicts = [das_batch.verify_column_host(r.payload) for r in das_reqs]
            for r, v in zip(das_reqs, verdicts):
                results[id(r)] = bool(v)

        agg_reqs = [r for r in reqs if r.kind == "agg"]
        if agg_reqs:
            if device:
                from eth_consensus_specs_tpu.crypto.curve import g2_to_bytes
                from eth_consensus_specs_tpu.ops.g2_aggregate import sum_g2_many_device

                # _prep decompressed every member signature (or resolved
                # the future exceptionally — those were filtered out of
                # `reqs` as done), so prepped is the ragged point lists
                lists = [r.prepped for r in agg_reqs]
                max_lanes = max(len(pts) for pts in lists)
                # the LANE axis is what shards: a wide committee clears
                # the crossover even in a flush of one (the same LIVE
                # policy fn the front door routes by)
                sharded = mesh is not None and buckets.route_wide(
                    "agg", buckets.pow2_bucket(max_lanes), len(agg_reqs)
                )
                key = buckets.g2_agg_key(
                    len(agg_reqs), max_lanes, mesh=mesh if sharded else None
                )
                with buckets.first_dispatch(*key):
                    sums = sum_g2_many_device(
                        lists, mesh=mesh if sharded else None,
                        pad_shape=(key[1], key[2]),
                    )
                for r, p in zip(agg_reqs, sums):
                    results[id(r)] = g2_to_bytes(p)
            else:
                from eth_consensus_specs_tpu.crypto.signature import aggregate

                obs.count("serve.degraded_items",
                          sum(1 for r in agg_reqs if not r.canary))
                for r in agg_reqs:
                    results[id(r)] = aggregate(list(r.payload[0]))

        htr_reqs = [r for r in reqs if r.kind == "htr"]
        by_depth: dict[int, list[Request]] = {}
        for r in htr_reqs:
            by_depth.setdefault(r.payload[1], []).append(r)
        for depth, group in sorted(by_depth.items()):
            if device:
                from eth_consensus_specs_tpu.ops.merkle import merkleize_many_device

                trees = [r.prepped if r.prepped is not None else r.payload[0] for r in group]
                sharded = (
                    mesh is not None
                    and len(group) >= mesh_ops.min_items()
                    and buckets.mesh_dispatch_worthwhile(1 << depth, len(group))
                )
                # mesh-sharded dispatch pads the tree axis to the
                # per-shard bucket (not the global pow2) and signs the
                # compile key with the mesh signature so warmup
                # artifacts stay honest across mesh shapes; the key
                # comes from the LIVE key fn jaxlint's injectivity
                # check runs against (serve/buckets.merkle_many_key)
                key = buckets.merkle_many_key(
                    len(group), depth, self.config.buckets,
                    mesh=mesh if sharded else None,
                )
                with buckets.first_dispatch(*key):
                    roots = merkleize_many_device(
                        trees, depth, pad_batch=key[1],
                        mesh=mesh if sharded else None,
                    )
            else:
                from eth_consensus_specs_tpu.obs.watchdog import host_tree_root_words
                from eth_consensus_specs_tpu.ops.merkle import _chunks_to_words

                obs.count("serve.degraded_items",
                          sum(1 for r in group if not r.canary))
                roots = [
                    host_tree_root_words(
                        r.prepped
                        if r.prepped is not None
                        else _chunks_to_words(r.payload[0], 1 << depth)
                    )
                    for r in group
                ]
            for r, root in zip(group, roots):
                results[id(r)] = root

        slot_reqs = [r for r in reqs if r.kind == "slot"]
        if slot_reqs:
            # stateful: slots serialize against ONE world (serve/slot.py
            # locks and commits all-or-nothing; the degrade ladder and
            # the slot.verify/slot.reroot fault sites live INSIDE
            # execute, so the device/host legs here are the same call —
            # idempotent re-execution after a serve.dispatch degrade
            # replays committed slots from the dedup window). The three
            # phase walls ride the request into the waterfall at resolve.
            world = self.slot_world()
            if not device:
                obs.count("serve.degraded_items", len(slot_reqs))
            for r in slot_reqs:
                result, phases = world.execute(r.payload, r.prepped, mesh=mesh)
                r.slot_phases = phases
                results[id(r)] = result

        shuffle_reqs = [r for r in reqs if r.kind == "shuffle"]
        if shuffle_reqs:
            from eth_consensus_specs_tpu.ops import shuffle

            rounds = shuffle.mainnet_rounds()
            if not device:
                obs.count("serve.degraded_items", len(shuffle_reqs))
            # the op takes the device only for a compiled lane bucket
            route = shuffle.shuffled_indices if device else shuffle.shuffled_indices_host
            for r in shuffle_reqs:
                results[id(r)] = route(*r.payload, rounds)

        for r in reqs:
            if r.kind != "state_root":
                continue
            arrays, meta, balances, eff, inact, just = r.payload
            if device:
                from eth_consensus_specs_tpu.ops.state_root import (
                    post_epoch_state_root,
                    state_root_compile_key,
                )

                with buckets.first_dispatch(*state_root_compile_key(meta)):
                    # np.asarray IS the sync: the device stage's clock
                    # closes only once the root words are host-resident
                    results[id(r)] = np.asarray(
                        post_epoch_state_root(arrays, meta, balances, eff, inact, just)
                    )
            else:
                from eth_consensus_specs_tpu.ops.state_root import post_epoch_state_root_host

                obs.count("serve.degraded_items", 1)
                results[id(r)] = np.asarray(
                    post_epoch_state_root_host(arrays, meta, balances, eff, inact, just)
                )
        return results

    def _signer_keys(self, signers) -> list:
        """A BLS request's signers as 48-byte keys, for the host oracle."""
        if isinstance(signers, np.ndarray):
            return [row.tobytes() for row in self._keys.compressed[signers]]
        return signers

    def _release_once(self, req: Request, service_s: float | None = None) -> None:
        """Each request's admission slot is released exactly once, however
        many paths observe its end (prep failure, cancellation sweep,
        dispatch resolution) — double release would undercount live load
        and let admission overshoot the caps."""
        if req.released:
            return
        req.released = True
        if req.canary:
            return  # never admitted: nothing to release, no EWMA sample
        self.admission.release(req.cost_bytes, service_s)

    def _resolve(
        self, req: Request, value=None, exc: BaseException | None = None,
        service_s: float | None = None, legs: dict | None = None,
    ) -> None:
        self._release_once(req, service_s)
        waterfall.mark(req.stamps, "resolved")
        # fold the stamp vector into the per-stage histograms, and stash
        # the DURATIONS by trace id for the RPC layer — monotonic stamps
        # don't cross a process boundary, durations do (obs/waterfall.py).
        # The stash MUST land before the future resolves: the RPC handler
        # blocked on fut.result() pops by trace id the instant it wakes,
        # and a pop that beats the stash ships the reply without stages
        durations = waterfall.stage_durations_ms(req.t_submit, req.stamps)
        # the legs of the flush that served it split its device stage
        waterfall.add_legs(durations, legs)
        # the slot pipeline's three phase walls (slot.verify /
        # slot.aggregate / slot.reroot) ride the SAME stage histograms
        # and the same per-trace stash the replica wire ships
        phases = getattr(req, "slot_phases", None)
        if phases:
            durations = {**durations, **phases}
        if durations:
            waterfall.observe(durations)
            if req.trace is not None:
                waterfall.stash(req.trace.trace_id, durations)
        try:
            if exc is not None:
                req.future.set_exception(exc)
            else:
                req.future.set_result(value)
        except Exception:
            # a caller cancelled the pending future: its slot is already
            # released above; the worker threads must outlive the rudeness
            obs.count("serve.cancelled", 1)

    # ------------------------------------------------------------- admin --

    def stats(self) -> dict:
        p50 = self._waits.quantile(0.5)
        p99 = self._waits.quantile(0.99)
        counters = obs.snapshot()["counters"]
        # first-dispatch compile walls (process-wide histogram: every
        # service and precompile() in this process records into it)
        ch = obs.histogram("serve.compile_ms")
        compile_ms = None
        if ch is not None and ch.count:
            compile_ms = {
                "count": ch.count,
                "p50": round(ch.quantile(0.5), 3),
                "p99": round(ch.quantile(0.99), 3),
            }
        out = {
            "compile_ms": compile_ms,
            "queue_depth": self.admission.depth(),
            "in_flight_bytes": self.admission.in_flight_bytes(),
            "wait_samples": self._waits.count,
            "p50_wait_ms": round(p50, 3) if p50 is not None else None,
            "p99_wait_ms": round(p99, 3) if p99 is not None else None,
            "flushes": {
                reason: counters.get(f"serve.flush.{reason}", 0)
                for reason in ("size", "deadline", "pressure", "idle", "close")
            },
            "compiles": counters.get("serve.compiles", 0),
            "rejected": counters.get("serve.rejected", 0),
        }
        world = self._slot_world
        if world is not None:
            out["slot"] = world.status()
        return out

    def precompile(self, keys: list[tuple] | None = None, path: str | None = None) -> int:
        """Warm the compile cache from the persistent warmup list (or an
        explicit shippable artifact ``path``, or explicit keys) before
        taking traffic. Mesh-signed keys resolve against THIS service's
        dispatch mesh (``mesh_chips``), not the host-wide default. A
        ``("bls_keysum", items, lanes, registry)`` key warms the committee
        sums of flushes of that bucket over the registered registry: only
        a warmed bucket's sums go to the device (ops/bls_batch.py). A
        ``("das_msm", items, lanes)`` key and the ``("fr_fft", rows, 64)``
        key beside it do the same for a flush of data column sidecars
        (ops/das_batch.py), and a ``("shuffle", lanes)`` key for the
        committee requests whose active count that lane bucket holds
        (ops/shuffle.py)."""
        return buckets.precompile(
            keys, path=path, chips=self.config.mesh_chips or None, key_table=self._keys
        )

    def close(self, timeout: float = 30.0) -> None:
        """Drain queued requests (a final ``close`` flush), stop both
        threads, emit the run-level ``serve.stats`` event."""
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        self._batcher.close()
        self._batch_thread.join(timeout=timeout)
        self._dispatch_thread.join(timeout=timeout)
        st = self.stats()
        obs.event(
            "serve.stats",
            name=self.name,
            p50_wait_ms=st["p50_wait_ms"] or 0.0,
            p99_wait_ms=st["p99_wait_ms"] or 0.0,
            rejected=st["rejected"],
            compiles=st["compiles"],
        )

    def __enter__(self) -> "VerifyService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
