"""Central metric catalog: every counter/gauge/histogram/span name.

Declaring names in one place buys two machine checks the hand-maintained
way kept losing:

  * the ``obs-discipline`` speclint rule (analysis/lint.py) fails the
    build when code emits a metric name absent from this catalog — new
    instrumentation lands HERE first, with a help string, where a
    reviewer and a dashboard can see it;
  * :func:`eth_consensus_specs_tpu.obs.export.validate_text` rejects
    expositions containing families this catalog doesn't know — a
    renamed counter breaks CI instead of silently orphaning every
    recording rule and SLO that referenced the old name.

A ``*`` segment matches one or more name characters (``watchdog.*.checks``
covers ``watchdog.sha256.checks``); patterns exist for the families that
are keyed by kernel/op/site at runtime. The ``t.*`` / ``test.*``
namespaces are sanctioned scratch space for tests — production code may
not emit into them (the lint rule has no such carve-out; only the
exposition validator does).
"""

from __future__ import annotations

import re
from dataclasses import dataclass


@dataclass(frozen=True)
class Metric:
    kind: str  # "counter" | "gauge" | "histogram" | "span"
    name: str  # dotted obs name; '*' segments are runtime-keyed
    help: str


def _c(name: str, help: str) -> Metric:
    return Metric("counter", name, help)


def _g(name: str, help: str) -> Metric:
    return Metric("gauge", name, help)


def _h(name: str, help: str) -> Metric:
    return Metric("histogram", name, help)


def _s(name: str, help: str) -> Metric:
    return Metric("span", name, help)


CATALOG: tuple[Metric, ...] = (
    # ------------------------------------------------------------ kernels --
    _c("sha256.compressions", "sha256 compression function evaluations"),
    _c("sha256.dispatches", "device sha256 kernel dispatches"),
    _c("sha256.messages", "messages hashed through the tiled kernel"),
    _s("sha256.tiled", "tiled device sha256 dispatch"),
    _c("merkle.leaf_chunks", "leaf chunks merkleized"),
    _c("merkle.real_hashes", "non-padding hashes in merkle trees"),
    _c("merkle.trees", "merkle trees computed"),
    _s("merkle.subtree_root", "single-tree device merkleization"),
    _s("merkle.many_subtree_root", "vmapped multi-tree device merkleization"),
    _c("shuffle.decision_hashes", "swap-or-not decision hashes of live chunks"),
    _c("shuffle.lanes", "live shuffle lanes processed (the active count, not its bucket)"),
    _c("shuffle.permutations", "full committee permutations"),
    _s("shuffle.permutation", "device shuffle permutation"),
    _s("shuffle.pack", "leg: the rounds' pivots, the seed's words, the indices padded to the lane bucket"),
    _s("shuffle.call", "leg: host clock round the synced shuffle program (transfer in, the list ready)"),
    _s("shuffle.unpack", "leg: the shuffled list to the host, cut to the active count"),
    _c("g1_msm.scalar_steps",
       "sequential trips of G1 scalar loops (table steps, then windows), an execution"),
    _c("g1_msm.field_muls",
       "Montgomery multiplies a lane of G1 scalar loops, from the static shape"),
    _c("state_root.real_hashes", "hashes in post-epoch state roots"),
    _c("state_root.chain_steps", "sequential hash steps of state roots' list tails"),
    _c("state_root.roots", "post-epoch state roots computed"),
    _c("state_root.traces", "state-root kernel (re)traces"),
    _s("state_root.post_epoch", "device post-epoch state root"),
    _s("state_root.launch", "leg: the state-root program's call until it returns"),
    _s("state_root.wait", "leg: blocking on the state root"),
    _s("state_root.post_epoch_host", "host-oracle post-epoch state root"),
    _c("state_root.inc_roots", "incremental (forest) post-epoch state roots"),
    _c("state_root.inc_real_hashes",
       "dirty-path hashes in incremental state roots (capacity model)"),
    _c("merkle_inc.updates", "incremental forest path-update dispatches"),
    _c("merkle_inc.dirty_leaves", "live dirty leaves through forest updates"),
    _c("merkle_inc.real_hashes",
       "hashes in incremental forest updates (capacity model)"),
    _s("merkle_inc.update", "incremental dirty-subtree forest update"),
    _s("resident.run_epochs", "device-resident chained epoch advance"),
    # ------------------------------------------- durable resident state --
    _c("resident.checkpoints", "durable checkpoints committed"),
    _c("resident.checkpoint_blobs_written", "checkpoint blobs written+verified"),
    _c("resident.checkpoint_blobs_reused",
       "checkpoint blobs reused by content address"),
    _c("resident.torn_writes", "checkpoint writes failing read-back verify"),
    _c("resident.restores", "digest-verified checkpoint restores"),
    _c("resident.reingests", "full deterministic re-ingests (restore/scrub fallback)"),
    _c("resident.scrub.checks", "scrub subtree+upper-region integrity checks"),
    _c("resident.scrub.mismatches", "scrub checks that found corruption"),
    _c("resident.scrub.quarantines", "quarantine-and-rebuild passes after scrub hits"),
    _s("resident.checkpoint", "content-addressed forest checkpoint write"),
    _s("resident.restore", "digest-verified forest restore"),
    _s("resident.scrub", "salted-subtree resident integrity scrub"),
    _c("block_epoch.blocks_ingested", "blocks ingested into the chain kernel"),
    _c("block_epoch.epochs", "epoch transitions in block_epoch chains"),
    _c("block_epoch.ingests", "block_epoch ingest calls"),
    _c("block_epoch.slots", "slots advanced in block_epoch chains"),
    _c("block_epoch.traces", "block_epoch kernel (re)traces"),
    _c("block_epoch.validator_slots", "validator-slots processed"),
    _s("block_epoch.chain", "device block/epoch chain run"),
    _s("block_epoch.chain_host", "host-oracle block/epoch chain run"),
    # ---------------------------------------------------------------- bls --
    _c("bls.batch_items", "items in batched aggregate verifications"),
    _c("bls.batches", "batched aggregate verification calls"),
    _c("bls.fast_aggregate_verifies", "FastAggregateVerify calls"),
    _c("bls.messages_distinct", "distinct messages across a batch"),
    _c("bls.pairing_inputs", "pairing inputs accumulated"),
    _c("bls.pairings", "pairing evaluations"),
    _c("bls.pubkeys_aggregated", "pubkeys aggregated"),
    _c("bls.verify_many_items", "items through verify_many"),
    _s("bls.batch_verify", "batched RLC aggregate verification"),
    _s("bls.fast_aggregate_verify", "single FastAggregateVerify"),
    _s("bls.verify_many", "multi-item verify_many with bisection"),
    _h("bls.rlc_check_ms", "one random-linear-combination pairing check, ms (a sample a check)"),
    _h("bls.key_decode_ms",
       "the parse of a served flush in which a public key was decompressed, ms"),
    _s("bls.keys", "leg: a flush's signers to registry indices or points, signatures decompressed"),
    _s("bls.g1_sum.call", "leg: host clock round the committee sums (synced device call or C core)"),
    _s("bls.g1_sum.unpack", "leg: sums to affine, the 64-bit RLC multiply of each"),
    _s("bls.h2c", "leg: hash-to-G2 of an RLC check's distinct messages"),
    _s("bls.g2_fold", "leg: sum of r_i * sig_i of one RLC check"),
    _s("bls.pairing", "leg: the pairing check of one RLC check"),
    # ---------------------------------------------------------------- agg --
    _c("agg.committees", "committee contributions aggregated (tier 0)"),
    _c("agg.signatures", "member signatures through the committee tree"),
    _c("agg.subnet_partials", "per-(subnet, root) partial aggregates (tier 1)"),
    _c("agg.global_aggregates", "per-root global aggregates (tier 2)"),
    _c("agg.isolated_invalid", "invalid subnet partials isolated by bisection"),
    _g("agg.registry_validators", "validators in the live aggregation registry"),
    _h("agg.compile_ms", "G2 aggregation kernel first-dispatch compile wall ms"),
    _s("agg.slot", "one slot's committee-tree aggregation"),
    # ---------------------------------------------------------------- kzg --
    _c("kzg.batches", "RLC-combined blob KZG batch checks (one MSM + pairing each)"),
    _c("kzg.blobs_verified", "blobs through verify_many_blobs / the batch verifier"),
    _c("kzg.fft_rows", "blob polynomials through the batched device inverse FFT"),
    _c("kzg.isolated_invalid", "invalid blobs isolated by RLC bisection"),
    _s("kzg.verify_many", "batched blob KZG verification with bisection"),
    _s("kzg.brp", "leg: bit reversal of a flush's rows, roots of unity"),
    _s("kzg.horner", "leg: Horner over a flush's monomial coefficients"),
    _s("kzg.rlc_fold", "leg: Fiat-Shamir hash, powers and lane lists of one RLC check"),
    _s("kzg.pairing", "leg: the routed pairing check of one RLC check"),
    _s("fr_fft.pack", "leg: integers reduced mod r and cut into plain limbs, one array a flush"),
    _s("fr_fft.call", "leg: host clock round the synced batched-FFT device call"),
    _s("fr_fft.unpack", "leg: plain limbs of the real rows joined back to integers"),
    _s("g1_msm.pack", "leg: points and scalars to limbs and bits"),
    _s("g1_msm.call", "leg: host clock round the synced multi-MSM device call"),
    _s("g1_msm.unpack", "leg: Jacobian results to affine points"),
    # ---------------------------------------------------------------- das --
    _g("das.blobs", "blobs in the live DAS bench flush"),
    _c("das.flushes", "DAS bench blob-verification flushes"),
    _c("das.columns_verified", "data column sidecars through verify_many_columns"),
    _c("das.fft_rows", "cells interpolated, rows of a flush's one inverse FFT of 64 points"),
    _c("das.fold_rows_device", "cells weighted and added a sidecar inside the device program"),
    _c("das.boundary_ints",
       "Python integers made from or for the interpolation's arrays, counted from shapes"),
    _c("das.isolated_invalid", "invalid data column sidecars isolated by bisection"),
    _s("das.verify_many", "batched data column sidecar verification with bisection"),
    _s("das.fold", "leg: dedup, Fiat-Shamir challenge and powers, the cells' bytes as one array"),
    _s("das.interp_fold",
       "leg: a sidecar's weighted sum of interpolation coefficients and coset unshift: the device "
       "program's weights, segment ids and column indices, or the fold itself on the host route"),
    _s("das.check", "leg: one check of a run of sidecars: sums, RLC, RLI, the pairing"),
    _h("das.msm_call_ms",
       "a flush's per-sidecar proof sums, ms: ONE multi-MSM execution (a sample an execution)"),
    _h("das.rlc_check_ms", "one check of the verification equation, ms (a sample a check)"),
    # ------------------------------------------------------------- fault --
    _c("fault.degraded", "device->host degradations"),
    _c("fault.degraded.*", "degradations per site"),
    _c("fault.injected", "injected faults fired"),
    _c("fault.retries", "fault.retrying attempts"),
    # --------------------------------------------------------------- gen --
    _c("gen.bytes_serialized", "vector bytes serialized"),
    _c("gen.cases_*", "case outcomes by status (written/failed/skipped/...)"),
    _c("gen.parts", "vector parts written"),
    _c("gen.result_stream_errors", "malformed worker result frames"),
    _c("gen.torn_writes", "read-back-verification catches"),
    _c("gen.workers_recycled", "pool workers recycled at case cap"),
    _c("gen.workers_replaced", "dead/hung pool workers respawned"),
    _s("gen.case", "one generation case"),
    # --------------------------------------------------------- multihost --
    _c("multihost.init_failures", "jax.distributed init failures"),
    _c("multihost.initializations", "jax.distributed initializations"),
    _c("multihost.meshes_flat", "flat device meshes built"),
    _c("multihost.meshes_hybrid", "hybrid device meshes built"),
    _c("multihost.processes", "processes seen at mesh build"),
    _c("multihost.slice_remainder", "rows beyond an even host_local_slice shard split"),
    _s("multihost.initialize", "jax.distributed initialization"),
    # -------------------------------------------------------------- mesh --
    _c("mesh.dispatches", "mesh-sharded kernel dispatches"),
    _c("mesh.sharded_items", "live items (trees/MSM items/pairs) through sharded kernels"),
    _g("mesh.devices", "devices in the live serve mesh"),
    # ------------------------------------------------------------- serve --
    _c("serve.batch_items", "requests across all flushes"),
    _c("serve.cancelled", "futures cancelled by callers"),
    _c("serve.compiles", "first dispatches of a new bucket shape"),
    _c("serve.compiles_after_warmup", "bucket compiles after the warmup phase"),
    _c("serve.degraded_items", "requests served by host oracles"),
    _c("serve.flushes", "micro-batcher flushes"),
    _c("serve.flush.*", "flushes by reason (size/deadline/pressure/idle/close)"),
    _c("serve.precompiled", "bucket shapes warmed by precompile()"),
    _c("serve.rejected", "admission sheds"),
    _c("serve.rejected.*", "admission sheds by reason (queue/bytes)"),
    _c("serve.requests", "submits admitted"),
    _c("serve.requests.*", "submits by kind (bls/htr/state_root/das)"),
    _g("serve.in_flight_bytes", "admitted payload bytes in flight"),
    _g("serve.queue_depth", "admitted requests queued + in flight"),
    _h("serve.compile_ms", "first-dispatch compile wall ms"),
    _h("serve.compile_ms.*", "first-dispatch compile wall ms per op"),
    _h("serve.wait_ms", "request wait from submit to flush, ms"),
    _h("serve.stage_ms.*",
       "per-request waterfall stage ms (admit/queue/prep/handoff/dispatch_wait/"
       "device/resolve/other/total, plus the front door's wire residual); "
       "device.<leg> and device.other split the device stage (waterfall.leg)"),
    _s("serve.dispatch", "one batched device dispatch"),
    _s("serve.batch_wait", "the batch thread waiting for a request or its deadline"),
    _s("serve.prep", "host prep of one flush on the batch thread"),
    # set-up: what a process pays before its first request, beside XLA's
    # phases (xla.*_ms below). The two histograms are the walls that no
    # phase of XLA's holds, for the benchmark's setup_keys_s
    _h("serve.setup_ms.register_pubkeys",
       "register_pubkeys wall ms: the registry decoded, KeyValidated, indexed"),
    _h("serve.setup_ms.key_table.to_device",
       "the registry's Montgomery limbs made and placed on the device, ms"),
    _s("serve.register_pubkeys", "the registry's public keys handed over (keys=n)"),
    _s("key_table.validate", "leg: KeyValidate of every key of the registry"),
    _s("key_table.to_device",
       "leg: limb split and device_put of the key table at the first device_limbs, waited on"),
    _s("precompile.*",
       "leg: one key of precompile(), per op; an op's own leg further in keeps its compiles"),
    _s("native.load",
       "a C core's shared object found fresh by its digest, or built with cc (core=)"),
    # ---------------------------------------------------------------- hbm --
    _g("hbm.resident_bytes.*", "ledger-registered device bytes per owner"),
    _g("hbm.resident_bytes_total", "ledger-registered device bytes, all owners"),
    _c("hbm.registrations", "HBM ledger buffer registrations"),
    _c("hbm.donations", "HBM ledger buffers closed by jit donation"),
    _c("hbm.deletions", "HBM ledger buffers closed by deletion"),
    # ------------------------------------------------ whole-slot pipeline --
    _c("slot.slots", "whole-slot requests committed by the slot world"),
    _c("slot.attestations", "attestations carried by committed slots"),
    _c("slot.blobs", "blob sidecars carried by committed slots"),
    _c("slot.replays", "committed slots replayed from the dedup window"),
    _c("slot.host_folds", "slots degraded to the sequential host fold"),
    _c("slot.forest_rebuilds",
       "resident forests rebuilt from committed columns after a consumed "
       "donation (mid-dispatch device death recovery)"),
    # --------------------------------------------------------- frontdoor --
    _c("frontdoor.backoffs", "router backoffs honored"),
    _c("frontdoor.cancelled", "front-door futures cancelled"),
    _c("frontdoor.corrupt_frames", "corrupt frames detected at the wire"),
    _c("frontdoor.corrupt_retries", "corrupt-frame resends"),
    _c("frontdoor.degraded_to_host", "requests served by the front-door host oracle"),
    _c("frontdoor.duplicates_suppressed", "hedge duplicates suppressed"),
    _c("frontdoor.failovers", "requests failed over to a sibling"),
    _c("frontdoor.hedge_abandoned", "hedge legs abandoned (primary owns the slot)"),
    _c("frontdoor.hedge_wins", "hedge legs that resolved first"),
    _c("frontdoor.hedges", "hedged re-dispatches launched"),
    _c("frontdoor.planned_restarts", "zero-shed drain rollovers"),
    _c("frontdoor.probe_failures", "supervisor health-probe failures"),
    _c("frontdoor.replicas_grown", "replicas added by the SLO autoscaler"),
    _c("frontdoor.replicas_replaced", "dead replicas respawned"),
    _c("frontdoor.replicas_retired", "idle replicas retired by the SLO autoscaler"),
    _c("frontdoor.replies_dropped", "replica replies to vanished callers"),
    _c("frontdoor.request_errors", "typed application errors returned"),
    _c("frontdoor.requests", "front-door submits"),
    _c("frontdoor.requests.*", "front-door submits by kind"),
    _c("frontdoor.respawn_failures", "replica respawn attempts that failed"),
    _c("frontdoor.route.affinity", "requests routed to their shape-affine replica"),
    _c("frontdoor.route.fallback", "requests routed past their affine replica"),
    _c("frontdoor.route.mesh_affinity",
       "requests routed to the mesh tier matching their width"),
    _c("frontdoor.route.warm",
       "requests routed to a replica already warm for their shape"),
    _c("frontdoor.slo_sheds", "SLO-driven admission shrinks"),
    _g("frontdoor.effective_max_queue", "SLO-adjusted admission cap"),
    _g("frontdoor.replicas", "replicas currently in rotation"),
    _h("frontdoor.e2e_ms", "front-door end-to-end latency, ms"),
    _s("frontdoor.rpc", "one framed RPC at the replica boundary"),
    # --------------------------------------------------------- slo burn --
    _c("slo.windows", "supervision probe windows with wait samples"),
    _c("slo.windows_breached",
       "probe windows whose window-local wait p99 breached the objective"),
    # --------------------------------------------- continuous telemetry --
    _c("tsdb.samples", "telemetry windows folded into the series ring"),
    _c("telemetry.errors", "guarded telemetry-tick failures (never fatal)"),
    _c("anomaly.fires", "anomalies fired (post refractory suppression)"),
    _c("anomaly.fires.*", "anomaly fires per detector"),
    _c("anomaly.suppressed", "anomalies suppressed by the refractory window"),
    _c("anomaly.errors", "detector step exceptions swallowed"),
    _c("canary.sent", "known-answer canary requests injected"),
    _c("canary.sent.*", "canary sends per shape (bls/htr/agg/kzg)"),
    _c("canary.ok", "canaries whose result matched the host oracle bit-exactly"),
    _c("canary.parity_failures",
       "canaries whose result MISMATCHED the host oracle (page-level)"),
    _c("canary.errors", "canaries that errored or timed out (degraded, not wrong)"),
    _c("canary.requests", "canary submits through the service pipeline"),
    _c("canary.host_served", "canaries absorbed by the front-door host oracle"),
    _g("canary.pass_rate", "ok / completed canaries, cumulative"),
    _h("canary.wait_ms", "canary wait from submit to flush, ms"),
    _h("canary.e2e_ms", "canary front-door end-to-end latency, ms"),
    # ---------------------------------------------------------- watchdog --
    _c("watchdog.checks", "device/host divergence probes"),
    _c("watchdog.divergences", "device/host mismatches"),
    _c("watchdog.*.checks", "divergence probes per kernel"),
    _c("watchdog.*.divergences", "mismatches per kernel"),
    # ------------------------------------------------------------- xprof --
    _c("xprof.analysis_unavailable", "XLA analyses missing on this backend"),
    _c("xprof.cost_model_mismatch", "hand work_bytes outside tolerance of XLA"),
    _c("xprof.cost_model_mismatch.*", "cost-model mismatches per kernel"),
    _g("xprof.*.*", "per-kernel XLA cost/memory attribution (flops, bytes_accessed, peak_bytes, ...)"),
    _h("xprof.compile_ms", "AOT compile wall ms"),
    _h("xprof.compile_ms.*", "AOT compile wall ms per kernel"),
    # the four phases of a compile as jax.monitoring reports them, by the
    # waterfall leg open on the compiling thread (none outside one); each
    # compile_ms sample also emits an `xla.compile` event (fun_name, leg, ms,
    # cache_hit, trace_ms, lower_ms, cache_read_ms) into the ring and the JSONL
    _h("xla.trace_ms.*",
       "jaxpr traces, ms: the outermost trace on a thread alone (a nested jit's "
       "lies inside its caller's)"),
    _h("xla.lower_ms.*", "jaxpr to MLIR module lowerings, ms"),
    _h("xla.cache_read_ms.*",
       "persistent-cache hits, ms: read, deserialise and load of the executable "
       "(inside the hit's xla.compile_ms sample)"),
    _h("xla.compile_ms.*",
       "XLA backend-compile events (persistent-cache hits among them), ms; "
       "compiled anew is this less xla.cache_read_ms"),
    # ------------------------------------------------------------ flight --
    _c("flight.dumps", "postmortem bundles written"),
    # ---------------------------------------------------------- lockwatch --
    _c("lockwatch.inversions", "live lock-order inversions observed"),
    _g("lockwatch.acquisitions", "watched-lock acquisitions (published at epilogue)"),
    _g("lockwatch.edges", "distinct live lock-order edges (published at epilogue)"),
    # ------------------------------------------------------- cross-cutting --
    _c("*.bytes_moved", "device traffic attributed via obs.bytes_moved"),
)

# test scratch namespaces: allowed in EXPOSITIONS (tests write through the
# global registry on purpose), never emitted by package code (the lint
# rule checks package code against CATALOG alone)
_TEST_NAMESPACES = ("t.", "test.")

_BY_KIND: dict[str, list[Metric]] = {}
for _m in CATALOG:
    _BY_KIND.setdefault(_m.kind, []).append(_m)


def _pattern_re(name: str) -> re.Pattern:
    rx = "".join(
        re.escape(c) if c != "*" else r"[a-z0-9_.]+" for c in name
    )
    return re.compile("^" + rx + "$")


_KIND_RES: dict[str, list[re.Pattern]] = {
    kind: [_pattern_re(m.name) for m in ms] for kind, ms in _BY_KIND.items()
}


def declared(kind: str, name: str) -> bool:
    """Is `name` (possibly with '*' placeholders from an f-string emit
    site) covered by a catalog entry of `kind`? A placeholder is matched
    as a representative token, so ``serve.flush.*`` (emit site) matches
    the catalog's ``serve.flush.*`` and ``*.bytes_moved`` matches
    ``*.bytes_moved``."""
    sample = name.replace("*", "x0")
    return any(rx.match(sample) for rx in _KIND_RES.get(kind, ()))


# ------------------------------------------------------- exposition check --


def _prom_family_res() -> list[re.Pattern]:
    out: list[re.Pattern] = []
    for m in CATALOG:
        # prom-space: dots collapse to underscores, so '*' must match
        # underscores too (translate around the placeholder — the plain
        # metric_name() would collapse '*' itself to '_')
        prom = m.name.replace(".", "_")
        base = "".join(
            re.escape(c) if c != "*" else "[a-zA-Z0-9_]+" for c in prom
        )
        suffixes = {
            "counter": ("_total",),
            "gauge": ("", "_max"),
            "histogram": ("",),
            "span": ("_calls_total", "_seconds_total"),
        }[m.kind]
        for suf in suffixes:
            out.append(re.compile("^" + base + re.escape(suf) + "$"))
    for ns in _TEST_NAMESPACES:
        out.append(re.compile("^" + re.escape(ns.replace(".", "_")) + ".*$"))
    return out


_PROM_RES: list[re.Pattern] | None = None


def prom_family_known(family: str) -> bool:
    """Used by export.validate_text: is this Prometheus family name one
    the catalog (or the test scratch namespace) declares?"""
    global _PROM_RES
    if _PROM_RES is None:
        _PROM_RES = _prom_family_res()
    return any(rx.match(family) for rx in _PROM_RES)
