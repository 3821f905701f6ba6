"""envreg — the single registry of every ``ETH_SPECS_*`` environment knob.

Forty-plus env vars steer this codebase; before this registry they were
documented in three hand-maintained tables (docs/observability.md,
docs/serving.md, docs/robustness.md) that nothing diffed against the
code — a renamed or added knob silently rotted out of the operator's
view. Now:

  * every ``os.environ`` read of an ``ETH_SPECS_*`` name must have a
    declaration here — the ``env-registry`` speclint rule
    (analysis/lint.py) fails on undeclared reads AND on stale
    declarations nothing reads;
  * ``scripts/gen_env_docs.py`` generates docs/env-reference.md (the
    one table) from this registry; CI diffs generated vs committed, so
    the docs literally cannot drift;
  * the three per-subsystem docs pages link into the generated table
    instead of maintaining their own copies.

``default`` is the human-readable effective default (what an unset var
behaves like), not necessarily a parseable literal. ``anchor`` is the
docs page whose prose explains the knob in context.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class EnvVar:
    name: str
    default: str
    description: str
    anchor: str  # docs page (with optional #fragment) that explains it


def _v(name: str, default: str, description: str, anchor: str) -> EnvVar:
    return EnvVar(name, default, description, anchor)


ENV_VARS: tuple[EnvVar, ...] = (
    # -------------------------------------------------------------- obs --
    _v("ETH_SPECS_OBS", "1",
       "`0` disables all obs recording (read once at import; "
       "`obs.registry.refresh_enabled()` re-reads)", "observability.md"),
    _v("ETH_SPECS_OBS_WATCHDOG", "0.05",
       "divergence-watchdog sampling rate in [0, 1]; `0` off, `1` checks every "
       "call; the first call per kernel per process is always checked",
       "observability.md#divergence-watchdog"),
    _v("ETH_SPECS_OBS_JSONL", "unset",
       "stream structured events (spans, divergences, gen part digests) as "
       "JSON lines to this path", "observability.md"),
    _v("ETH_SPECS_OBS_REPORT", "`<rootdir>/obs_report.json`",
       "pytest run-level report destination; `0`/empty disables",
       "observability.md#reading-obs_reportjson"),
    _v("ETH_SPECS_OBS_PROM", "unset",
       "Prometheus textfile destination (written atomically by the pytest "
       "plugin and serve_bench at session end)",
       "observability.md#metrics-exposition-prometheus"),
    _v("ETH_SPECS_OBS_HTTP_PORT", "unset",
       "serve `GET /metrics` on 127.0.0.1:port (stdlib, daemon threads; `0` = "
       "ephemeral port)", "observability.md#metrics-exposition-prometheus"),
    _v("ETH_SPECS_OBS_POSTMORTEM_DIR", "unset",
       "flight-recorder bundle directory; unset makes every postmortem dump a "
       "no-op", "observability.md#flight-recorder"),
    _v("ETH_SPECS_OBS_FLIGHT", "512",
       "flight ring capacity (entries); `0` disables the ring",
       "observability.md#flight-recorder"),
    _v("ETH_SPECS_OBS_FLIGHT_COUNTER_FLOOR", "65536",
       "smallest counter increment that becomes a flight-ring entry",
       "observability.md#flight-recorder"),
    _v("ETH_SPECS_OBS_XPROF", "0",
       "`1` enables ambient XLA attribution capture on the instrumented "
       "kernels (AOT compile ≈ doubles per-shape compile cost)",
       "observability.md#compile--memory-attribution-xprof"),
    _v("ETH_SPECS_OBS_XPROF_TOL", "0.25",
       "cost-model rel-err tolerance before `xprof.cost_model_mismatch` fires",
       "observability.md#compile--memory-attribution-xprof"),
    _v("ETH_SPECS_SLO_WAIT_P99_MS", "250",
       "`serve_wait_p99` SLO bound, milliseconds", "observability.md#slos"),
    _v("ETH_SPECS_SLO_DEGRADED_RATE", "0.01",
       "`degraded_rate` SLO bound (`serve.degraded_items` per serve request)",
       "observability.md#slos"),
    # ----------------------------------------------- continuous telemetry --
    _v("ETH_SPECS_OBS_TSDB", "1",
       "`0`: disable the in-process metric time-series ring (and with it "
       "the anomaly detectors and scoreboard series)",
       "observability.md#continuous-telemetry"),
    _v("ETH_SPECS_OBS_TSDB_RING", "600",
       "telemetry samples the series ring retains (~2 minutes at the "
       "default 200 ms probe interval)",
       "observability.md#continuous-telemetry"),
    _v("ETH_SPECS_OBS_SCOREBOARD", "unset",
       "path the supervisor atomically rewrites a JSON fleet scoreboard "
       "to each telemetry tick (`scripts/obs_top.py --watch` tails it)",
       "observability.md#continuous-telemetry"),
    _v("ETH_SPECS_CANARY_MS", "0",
       "known-answer canary injection interval, ms (`0` = off); canaries "
       "ride the normal front-door path but are exempt from admission "
       "and excluded from SLO/throughput stats",
       "observability.md#continuous-telemetry"),
    _v("ETH_SPECS_CANARY_TIMEOUT_S", "10",
       "a canary unresolved past this counts as `canary.errors` "
       "(degraded, not a parity failure)",
       "observability.md#continuous-telemetry"),
    _v("ETH_SPECS_CANARY_SHAPES", "bls,htr,agg",
       "canary shape cycle (csv of bls/htr/agg/kzg, or `all`); `kzg` is "
       "opt-in because each probe costs a 4096-element blob parse",
       "observability.md#continuous-telemetry"),
    _v("ETH_SPECS_ANOM_DETECTORS", "all",
       "anomaly detector set: `all`, `structural` (deterministic fault "
       "signatures — the bench clean-run gate), `none`, or a csv of "
       "detector names", "observability.md#continuous-telemetry"),
    _v("ETH_SPECS_ANOM_WARMUP", "12",
       "traffic windows before the statistical detectors arm",
       "observability.md#continuous-telemetry"),
    _v("ETH_SPECS_ANOM_K", "8",
       "`latency_step` deviation multiplier (EWMA MAD-proxy)",
       "observability.md#continuous-telemetry"),
    _v("ETH_SPECS_ANOM_CONFIRM", "2",
       "consecutive suspicious windows before a detector fires",
       "observability.md#continuous-telemetry"),
    _v("ETH_SPECS_ANOM_STALL_WINDOWS", "15",
       "dark windows before `completion_stall` / `dead_stage` fire",
       "observability.md#continuous-telemetry"),
    _v("ETH_SPECS_ANOM_DRIFT_RATIO", "3",
       "`latency_drift` fires when the p99 EWMA crosses this multiple of "
       "its warmup anchor", "observability.md#continuous-telemetry"),
    _v("ETH_SPECS_ANOM_RATE_RATIO", "8",
       "`rate_spike`/`rate_stall` baseline multiple",
       "observability.md#continuous-telemetry"),
    _v("ETH_SPECS_ANOM_BURN", "0.5",
       "windowed SLO burn rate that rates a `burn_accel` fire",
       "observability.md#continuous-telemetry"),
    _v("ETH_SPECS_ANOM_BURN_WINDOW_S", "30",
       "the `slo.burn_rate(window_s=...)` horizon `burn_accel` watches",
       "observability.md#continuous-telemetry"),
    _v("ETH_SPECS_ANOM_REFRACTORY_S", "30",
       "per-(detector, replica, stage) refire suppression window, seconds",
       "observability.md#continuous-telemetry"),
    _v("ETH_SPECS_OBS_TRACE_GAP_S", "120",
       "fleet-timeline episode split: a wall-clock gap wider than this "
       "separates re-used trace ids / slot numbers into distinct episodes",
       "observability.md#fleet-timeline--slot-autopsy"),
    _v("ETH_SPECS_SLOT_BUDGET_MS", "1000",
       "per-slot latency budget the slot autopsy renders its verdict "
       "against", "observability.md#fleet-timeline--slot-autopsy"),
    # ------------------------------------------------------------ serve --
    _v("ETH_SPECS_SERVE", "off",
       "`1`: gen pool workers route BLS verifies through a per-worker service "
       "(or the shared front door when `ETH_SPECS_SERVE_REPLICAS` > 0)",
       "serving.md#tuning-knobs"),
    _v("ETH_SPECS_SERVE_MAX_BATCH", "64",
       "size-flush threshold / largest bucket", "serving.md#tuning-knobs"),
    _v("ETH_SPECS_SERVE_MAX_WAIT_MS", "5",
       "deadline-flush latency bound", "serving.md#tuning-knobs"),
    _v("ETH_SPECS_SERVE_MAX_QUEUE", "1024",
       "admission cap, queued + in-flight requests", "serving.md#tuning-knobs"),
    _v("ETH_SPECS_SERVE_MAX_BYTES", "64 MiB",
       "admission cap, in-flight payload bytes", "serving.md#tuning-knobs"),
    _v("ETH_SPECS_SERVE_PRESSURE", "0.5",
       "pressure-flush fraction of `MAX_QUEUE`", "serving.md#tuning-knobs"),
    _v("ETH_SPECS_SERVE_BUCKETS", "1,2,…,64",
       "pow2 batch-count buckets", "serving.md#tuning-knobs"),
    _v("ETH_SPECS_SERVE_WARMUP", "unset",
       "persistent compiled-shape list (JSONL); `precompile()` replays it",
       "serving.md#tuning-knobs"),
    _v("ETH_SPECS_SERVE_IDLE_FLUSH", "off",
       "`1`: flush immediately when the dispatch pipeline is idle (single "
       "synchronous submitter; gen workers enable it automatically)",
       "serving.md#tuning-knobs"),
    _v("ETH_SPECS_SERVE_REPLICAS", "0",
       ">0: run R supervised replica processes behind the front door (gen "
       "pool boots one fleet for all workers)",
       "serving.md#replicated-front-door"),
    _v("ETH_SPECS_SERVE_FRONTDOOR", "unset",
       "comma-separated `host:port` replica addresses — client mode (exported "
       "by the owner for its workers)", "serving.md#replicated-front-door"),
    _v("ETH_SPECS_SERVE_HEDGE_MS", "250",
       "hedge deadline: re-dispatch an idempotent submit to a sibling past it "
       "(`0` disables hedging)", "serving.md#replicated-front-door"),
    _v("ETH_SPECS_SERVE_RPC_TIMEOUT_S", "60",
       "hard per-RPC timeout; past it the replica is failed over",
       "serving.md#replicated-front-door"),
    _v("ETH_SPECS_SERVE_PROBE_MS", "200",
       "supervisor health-probe / SLO-window interval",
       "serving.md#replicated-front-door"),
    _v("ETH_SPECS_SERVE_FD_CONCURRENCY", "16",
       "front-door dispatcher threads", "serving.md#replicated-front-door"),
    _v("ETH_SPECS_SERVE_SLO_SHED", "on",
       "`0`: disable SLO-driven admission resizing (static caps only)",
       "serving.md#replicated-front-door"),
    _v("ETH_SPECS_SERVE_CHIPS_MATRIX", "unset",
       "per-replica mesh-chip cycle (`1,8`): replica i owns "
       "`matrix[i % len]` chips — the heterogeneous two-tier fleet",
       "serving.md#two-tier-scale-out"),
    _v("ETH_SPECS_SERVE_DOWN_COOLDOWN_MS", "500",
       "half-open probe cooldown before a down replica gets a trial request",
       "serving.md#replicated-front-door"),
    _v("ETH_SPECS_SERVE_DRAINING_TTL_S", "5",
       "expiry of a client-OBSERVED `draining` reply (owner-asserted "
       "draining stays sticky)", "serving.md#replicated-front-door"),
    _v("ETH_SPECS_SERVE_AUTOSCALE", "0",
       "`1`: the SLO evaluator also drives replica COUNT — grow a pre-warmed "
       "replica on sustained breach, retire one on sustained idle",
       "serving.md#two-tier-scale-out"),
    _v("ETH_SPECS_SERVE_MIN_REPLICAS", "1",
       "autoscaler floor on replicas in rotation",
       "serving.md#two-tier-scale-out"),
    _v("ETH_SPECS_SERVE_MAX_REPLICAS", "8",
       "autoscaler ceiling on replicas in rotation",
       "serving.md#two-tier-scale-out"),
    _v("ETH_SPECS_SERVE_GROW_WINDOWS", "3",
       "consecutive breached probe windows before the autoscaler grows",
       "serving.md#two-tier-scale-out"),
    _v("ETH_SPECS_SERVE_RETIRE_WINDOWS", "10",
       "consecutive idle probe windows before the autoscaler retires",
       "serving.md#two-tier-scale-out"),
    _v("ETH_SPECS_SERVE_SCALE_COOLDOWN_S", "5",
       "minimum seconds between autoscaler actions",
       "serving.md#two-tier-scale-out"),
    _v("ETH_SPECS_SERVE_DISTRIBUTED", "0",
       "`1`: a spawned replica joins the multi-host runtime at boot "
       "(`jax.distributed` via parallel/multihost.py) so its mesh slice "
       "spans a pod, not a host", "serving.md#two-tier-scale-out"),
    _v("ETH_SPECS_SERVE_CHIPS", "0",
       "chips the serve dispatch mesh spans (0 = every local device; 1 = "
       "single-device dispatch); `serve_bench.py --chips` forces the matching "
       "virtual CPU device count", "serving.md#mesh-sharded-dispatch"),
    # --------------------------------------------- whole-slot pipeline --
    _v("ETH_SPECS_SLOT_VALIDATORS", "256",
       "registry size of the deterministic slot world `submit_slot` mutates "
       "(the ResidentOwner recipe: same size, bit-identical state)",
       "serving.md#whole-slot-pipeline"),
    _v("ETH_SPECS_SLOT_CKPT_DIR", "unset",
       "durable checkpoint store of the slot world: set on the OWNER replica "
       "(the front door strips it from siblings — one stateful member); every "
       "committed slot checkpoints before its result resolves",
       "serving.md#whole-slot-pipeline"),
    _v("ETH_SPECS_SLOT_DEDUP", "256",
       "applied-slot idempotency window: a retried committed slot replays its "
       "recorded result instead of double-applying (rides the checkpoint "
       "manifest's digest-covered extra payload)",
       "serving.md#whole-slot-pipeline"),
    _v("ETH_SPECS_SLOT_SYNC_REWARD", "1024",
       "per-participant gwei a VALID sync aggregate credits (the slot-level "
       "balance mutation both the device kernel and the host fold apply)",
       "serving.md#whole-slot-pipeline"),
    # --------------------------------------------- durable resident state --
    _v("ETH_SPECS_RESIDENT_CKPT_DIR", "unset",
       "checkpoint store for the durable resident state: set on a replica to "
       "make it own a digest-gated resident forest (restore at boot, "
       "checkpoint every interval, scrub on demand)",
       "robustness.md#durable-resident-state"),
    _v("ETH_SPECS_RESIDENT_VALIDATORS", "256",
       "validator count of the deterministic resident world the durable "
       "replica owns (seeded columns + synthetic static tree content)",
       "robustness.md#durable-resident-state"),
    _v("ETH_SPECS_RESIDENT_CKPT_INTERVAL", "2",
       "epochs between durable checkpoints during a resident advance "
       "(written outside the donated jit chain)",
       "robustness.md#durable-resident-state"),
    _v("ETH_SPECS_RESIDENT_SCRUB_K", "8",
       "salted subtrees re-hashed per scrub pass (per tree, plus the full "
       "upper region)", "robustness.md#durable-resident-state"),
    _v("ETH_SPECS_RESIDENT_RESTORE", "prefer",
       "boot restore policy: `prefer` degrades a torn/corrupt checkpoint to "
       "full re-ingest, `require` refuses to boot on one, `never` always "
       "cold-starts", "robustness.md#durable-resident-state"),
    # ------------------------------------------------------------- mesh --
    _v("ETH_SPECS_MESH", "1",
       "`0`: disable mesh-sharded kernel dispatch entirely (every entry point "
       "takes the bit-identical single-device path)",
       "serving.md#mesh-sharded-dispatch"),
    _v("ETH_SPECS_MESH_MIN_ITEMS", "2",
       "smallest live batch a sharded dispatch is worth; below it the "
       "single-device bucket path is cheaper than the mesh padding",
       "serving.md#mesh-sharded-dispatch"),
    _v("ETH_SPECS_MESH_SCALING_MIN", "0.7",
       "mesh bench gate: minimum per-effective-chip scaling factor "
       "(`serve_bench.py --chips N` fails below it)",
       "serving.md#mesh-sharded-dispatch"),
    # -------------------------------------------------------------- agg --
    _v("ETH_SPECS_AGG_SUBNETS", "64",
       "attestation subnets the committee-tree aggregation fans in over "
       "(mainnet's 64; the bench/registry builders partition committees by "
       "it)", "serving.md#aggregation-pipeline"),
    _v("ETH_SPECS_AGG_MESH_LANES", "8",
       "smallest ragged-committee lane count worth sharding the G2 "
       "aggregation dispatch's lane axis over the mesh; below it the "
       "all-gather combine costs more than the lanes it saves",
       "serving.md#aggregation-pipeline"),
    # -------------------------------------------------------------- kzg --
    _v("ETH_SPECS_KZG_MESH_LANES", "16",
       "smallest RLC lane count worth sharding the KZG blob-verification "
       "multi-MSM's lane axis over the mesh (a flush of n blobs folds into "
       "2n+1 lanes); below it the all-gather combine costs more than the "
       "double-and-add lanes it saves",
       "serving.md#blob-verification-pipeline"),
    _v("ETH_SPECS_KZG_HOST_EVAL", "0",
       "`1`: evaluate blob polynomials at the challenge point through the "
       "host barycentric oracle instead of the batched device inverse FFT "
       "(bit-identical values; the degrade/bench control for backends where "
       "the 4096-point FFT compile is not worth paying)",
       "serving.md#blob-verification-pipeline"),
    # -------------------------------------------- incremental merkle --
    _v("ETH_SPECS_INC_DIRTY_BUCKETS", "8,64,256,1024,4096,16384,65536",
       "pow2 dirty-leaf capacity buckets the incremental forest kernels "
       "compile under (serve-buckets idiom for the dirty axis)",
       "tpu.md#incremental-merkleization"),
    _v("ETH_SPECS_INC_CROSSOVER", "0.25",
       "sparse-vs-dense work-ratio crossover: fraction of hash-count "
       "break-even at which a forest update abandons the path-update for "
       "the dense rebuild (measured constant factor of the narrow-width "
       "gather/hash/scatter path)", "tpu.md#incremental-merkleization"),
    _v("ETH_SPECS_INC_SPEEDUP_MIN", "2.0",
       "resident-smoke gate: minimum incremental-vs-full state-root "
       "speedup factor (`scripts/resident_bench.py --speedup-min`)",
       "tpu.md#incremental-merkleization"),
    # ------------------------------------------------------------ fault --
    _v("ETH_SPECS_FAULT", "unset",
       "deterministic fault-injection spec: `site:mode[:key=value...]` rules "
       "joined by `;` (modes raise/kill/stall/corrupt)",
       "robustness.md#fault-spec-grammar"),
    # --------------------------------------------------------- analysis --
    _v("ETH_SPECS_ANALYSIS_LOCKWATCH", "0",
       "`1`: wrap project locks in the runtime lock-order watchdog "
       "(acquisition-order edges, inversion counters, static-graph "
       "cross-check)", "analysis.md#runtime-lock-order-watchdog"),
    _v("ETH_SPECS_ANALYSIS_CONST_MAX_BYTES", "1048576",
       "jaxlint constant-bloat threshold: largest literal constant a traced "
       "kernel body may bake into its jaxpr",
       "analysis.md#trace-level-rules-jaxlint"),
    _v("ETH_SPECS_ANALYSIS_DONATE_MIN_BYTES", "1048576",
       "jaxlint donation-audit threshold: an undonated input aliasing an "
       "output aval at or above this many bytes is a missed-donation finding",
       "analysis.md#trace-level-rules-jaxlint"),
    _v("ETH_SPECS_ANALYSIS_RANGE_WIDEN_STEPS", "12",
       "rangelint loop-widening budget: join-and-retry passes before a "
       "non-inductive scan/while carry is widened to dtype-top (an "
       "unproven-loop lane-overflow finding); sha256's 8-register "
       "rotation needs ~9",
       "analysis.md#value-range-rules-rangelint"),
    _v("ETH_SPECS_ANALYSIS_RANGE_TIMEOUT_S", "300",
       "rangelint per-family analysis deadline in seconds; exceeding it "
       "is itself a lane-overflow finding (the kernel remains unproven)",
       "analysis.md#value-range-rules-rangelint"),
    # ----------------------------------------------------------- kernels --
    _v("ETH_SPECS_TPU_NO_NATIVE", "0",
       "`1`: skip the native (CFFI) BLS fast paths, pure-python/device only",
       "tpu.md"),
    _v("ETH_SPECS_TPU_OBJECT_EPOCH", "0",
       "`1`: route epoch accounting through the object-mode reference path "
       "instead of the columnar kernel", "tpu.md"),
    # ------------------------------------------------------------- misc --
    _v("ETH_SPECS_ALLOW_UNPINNED", "0",
       "`1`: allow building spec modules from unpinned reference markdown "
       "(development only)", "testing.md"),
    _v("ETH_SPECS_REFERENCE", "unset",
       "path to a reference consensus-specs checkout for specc compilation",
       "testing.md"),
)


def by_name() -> dict[str, EnvVar]:
    return {v.name: v for v in ENV_VARS}


def names() -> set[str]:
    return {v.name for v in ENV_VARS}


def markdown_table(prefix: str | None = None) -> str:
    """The generated reference table (docs/env-reference.md body).
    ``prefix`` narrows to one subsystem (e.g. ``ETH_SPECS_SERVE``)."""
    rows = [v for v in ENV_VARS if prefix is None or v.name.startswith(prefix)]
    out = ["| variable | default | meaning | details |", "|---|---|---|---|"]
    for v in sorted(rows, key=lambda v: v.name):
        out.append(
            f"| `{v.name}` | {v.default} | {v.description} | "
            f"[{v.anchor.split('#')[0].removesuffix('.md')}]({v.anchor}) |"
        )
    return "\n".join(out) + "\n"
