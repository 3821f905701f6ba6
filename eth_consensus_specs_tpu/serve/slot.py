"""The serving-side owner of one whole-slot pipeline world.

``submit_slot`` is STATEFUL: unlike every other serve kind, a slot
request mutates the resident validator state it runs against. This
module owns that state inside one service/replica — the deterministic
resident world (seeded columns + synthetic static tree content, the
``ResidentOwner`` convention: same config → bit-identical state), the
resident merkle forest the slot chain donates through, and the commit
discipline that keeps the whole thing all-or-nothing:

  * **compute** — the three device phases (``slot.verify`` →
    ``slot.aggregate`` → ``slot.reroot``) run against the CURRENT
    carry; only the forest is donated, the columns are not, so a
    device death at any point leaves the committed state untouched.
  * **degrade** — the ladder (``fault.degrade`` at the ``slot.reroot``
    seam; both fault sites fire BEFORE any mutation) re-runs the WHOLE
    slot as the sequential host fold from the pre-slot columns. A
    half-applied slot is unrepresentable.
  * **commit** — durable-first: with a checkpoint dir configured, the
    post-slot state checkpoints (``ops/snapshot.py``, digest-gated,
    the applied-slot dedup window rides the manifest's digest-covered
    ``extra`` payload) BEFORE the result resolves. A SIGKILL before
    the checkpoint rolls the slot back — the client's retry re-applies
    it; a SIGKILL after resolves the retry from the restored dedup
    window instead of double-applying. Zero lost slots either way.

The world boots lazily on the first slot request (or eagerly via
:meth:`SlotWorld.boot` before a replica marks ready), restoring from
the latest checkpoint under the ``resident.restore`` degrade ladder
and prewarming the epoch-boundary + root kernels so slot serving never
cold-compiles after warmup."""

from __future__ import annotations

import json
import os
import threading
import time
from collections import OrderedDict
from dataclasses import replace
from functools import lru_cache

from eth_consensus_specs_tpu import fault, obs
from eth_consensus_specs_tpu.ops import slot_pipeline
from eth_consensus_specs_tpu.ops.slot_pipeline import SlotRequest, SlotResult

_DEFAULT_VALIDATORS = 256
_DEFAULT_DEDUP = 256
_STATS_FILE = "boot_stats.json"
# floor/fallback boot ETA before any measured boot exists
_DEFAULT_ETA_S = 2.0


def _result_json(r: SlotResult) -> dict:
    """A SlotResult as the JSON the checkpoint manifest's ``extra``
    payload carries (digest-covered, replayed verbatim on restore)."""
    return {
        "slot": int(r.slot),
        "att": [int(v) for v in r.att_verdicts],
        "sync": int(r.sync_verdict),
        "blob": [int(v) for v in r.blob_verdicts],
        "aggs": [[int(s), sig.hex()] for s, sig in r.subnet_aggregates],
        "root": r.state_root.hex(),
        "epoch": int(r.epoch),
    }


def _result_from_json(d: dict) -> SlotResult:
    return SlotResult(
        slot=int(d["slot"]),
        att_verdicts=tuple(bool(v) for v in d["att"]),
        sync_verdict=bool(d["sync"]),
        blob_verdicts=tuple(bool(v) for v in d["blob"]),
        subnet_aggregates=tuple(
            (int(s), bytes.fromhex(h)) for s, h in d["aggs"]
        ),
        state_root=bytes.fromhex(d["root"]),
        epoch=int(d["epoch"]),
    )


class SlotWorld:
    """Owner of the durable slot-pipeline state inside one service."""

    def __init__(
        self,
        n_validators: int = _DEFAULT_VALIDATORS,
        ckpt_dir: str = "",
        dedup_cap: int = _DEFAULT_DEDUP,
    ):
        self.n_validators = int(n_validators) or _DEFAULT_VALIDATORS
        self.ckpt_dir = ckpt_dir
        self.dedup_cap = max(int(dedup_cap), 1)
        self._lock = threading.RLock()
        self._booted = False
        self._boot_pending = False  # an EAGER boot is in flight
        self._boot_t0 = time.monotonic()
        self._eta_s = self._read_eta()
        self._spec = None
        self._static = None
        self._plan = None
        self._carry = None
        self._forest_consumed = False
        self._seq = 0  # slots committed (the manifest's epoch axis)
        self._epoch = 0  # ACCOUNTING epoch (advances on boundary slots)
        self._root = b""
        self._applied: OrderedDict[int, SlotResult] = OrderedDict()
        self._lineage: dict = {"verdict": "unbooted"}

    # ------------------------------------------------------------- boot --

    def _build_world(self):
        """The deterministic slot world — the exact ResidentOwner
        recipe, so cold re-ingest is a correct recovery leg here too."""
        import jax

        from eth_consensus_specs_tpu.ops.altair_epoch import example_altair_inputs
        from eth_consensus_specs_tpu.ops.state_root import synthetic_static

        self._spec = slot_pipeline.slot_spec()
        cols, just = example_altair_inputs(self.n_validators)
        self._static = synthetic_static(self._spec, self.n_validators)
        return jax.device_put(cols), jax.device_put(just)

    def _read_eta(self) -> float:
        try:
            with open(os.path.join(self.ckpt_dir, _STATS_FILE)) as f:
                return max(float(json.load(f).get("boot_s", 0.0)), 0.05)
        except (OSError, ValueError):
            return _DEFAULT_ETA_S

    def _persist_eta(self, seconds: float) -> None:
        try:
            os.makedirs(self.ckpt_dir, exist_ok=True)
            tmp = os.path.join(self.ckpt_dir, f"{_STATS_FILE}.__tmp{os.getpid()}")
            with open(tmp, "w") as f:
                json.dump({"boot_s": seconds}, f)
            os.replace(tmp, os.path.join(self.ckpt_dir, _STATS_FILE))
        except OSError:
            pass  # honesty stats are best-effort, never boot-fatal

    def mark_booting(self) -> None:
        """Declare an eager boot in flight BEFORE the replica socket
        starts answering: mid-boot slot submits then get an honest
        booting-busy (``busy`` + ``retry_after_s``) instead of parking
        in the listener backlog for the caller's whole RPC timeout. The
        lazy path (no eager boot) never sets this — a first request may
        still pay the boot inline, but it resolves rather than starves."""
        self._boot_pending = True
        self._boot_t0 = time.monotonic()

    @property
    def busy(self) -> bool:
        return self._boot_pending and not self._booted

    def retry_after_s(self) -> float:
        """Honest backoff for a submit that arrived mid-boot: the
        previously MEASURED boot wall minus the time already spent,
        floored — the ``ResidentOwner`` restore-ETA convention."""
        elapsed = time.monotonic() - self._boot_t0
        return max(round(self._eta_s - elapsed, 3), 0.05)

    def boot(self) -> None:
        """Idempotent synchronous boot: restore-or-ingest + prewarm.
        Call eagerly before a replica marks ready; otherwise the first
        slot request pays it (still before any result resolves)."""
        with self._lock:
            if self._booted:
                return
            t0 = time.monotonic()
            self._boot_inner()
            self._booted = True
            self._lineage["boot_ms"] = round((time.monotonic() - t0) * 1e3, 3)
            if self.ckpt_dir:
                self._persist_eta(time.monotonic() - t0)
            obs.event(
                "slot.boot",
                verdict=self._lineage.get("verdict", ""),
                slots=self._seq,
                epoch=self._epoch,
            )

    def _boot_inner(self) -> None:
        from eth_consensus_specs_tpu.ops import snapshot
        from eth_consensus_specs_tpu.parallel import resident
        from eth_consensus_specs_tpu.parallel.resident import ResidentCarry

        cols0, just0 = self._build_world()
        plan = resident.forest_plan_for(self._static)
        rs = None
        if self.ckpt_dir:

            def do_restore():
                found = snapshot.restore(self.ckpt_dir, static=self._static)
                if found is not None and tuple(found.plan)[:3] != tuple(plan)[:3]:
                    # registry-size/mesh drift under the same store is a
                    # config change, not damage: cold-start, don't degrade
                    obs.event(
                        "slot.checkpoint_plan_drift",
                        stored=list(found.plan)[:3],
                        current=list(plan)[:3],
                    )
                    return None
                return found

            rs = fault.degrade("resident.restore", do_restore, lambda: None)
        if rs is not None:
            self._carry = ResidentCarry(
                cols=rs.cols, just=rs.just, root_acc=None, forest=rs.forest
            )
            self._plan = rs.plan
            self._seq = int(rs.epoch)
            self._root = bytes.fromhex(rs.manifest["state_root"] or "")
            extra = (rs.manifest.get("extra") or {}).get("slot") or {}
            self._epoch = int(extra.get("epoch", 0))
            self._applied = OrderedDict(
                (int(d["slot"]), _result_from_json(d))
                for d in extra.get("applied", [])
            )
            self._lineage = {"verdict": "restored", "manifest": rs.digest}
        else:
            forest, built_plan = resident.build_state_forest_device(
                self._static, cols0
            )
            self._plan = built_plan
            self._carry = ResidentCarry(
                cols=cols0, just=just0, root_acc=None, forest=forest
            )
            self._seq = 0
            self._epoch = 0
            self._root = snapshot.state_root_bytes(
                self._static, self._plan, forest, just0
            )
            self._lineage = {"verdict": "cold"}
            if self.ckpt_dir:
                # establish LATEST durably so a pre-first-slot SIGKILL
                # restores the same base world (all blobs content-reuse)
                res = self._checkpoint_locked()
                self._lineage["manifest"] = res.digest
        self._prewarm()

    def _prewarm(self) -> None:
        """Compile the epoch-boundary chain + root gate on a throwaway
        forest COPY (run_epochs donates), and AOT-compile the slot_apply
        bucket a FULL slot of this registry lands in (every validator
        attests once an epoch, so a slot sets n / SLOTS_PER_EPOCH flags;
        a whole sync committee is credited) — after boot, slot serving's
        fixed-shape kernels never cold-compile."""
        import jax
        import numpy as np

        from eth_consensus_specs_tpu.ops import snapshot
        from eth_consensus_specs_tpu.parallel import resident

        carry = self._carry
        forest_copy = jax.tree_util.tree_map(
            lambda a: jax.device_put(np.asarray(a)), carry.forest
        )
        warm = resident.run_epochs(
            self._spec,
            carry.cols,
            carry.just,
            1,
            with_root="state_inc",
            static=self._static,
            forest=forest_copy,
        )
        snapshot.state_root_bytes(self._static, self._plan, warm.forest, warm.just)
        from eth_consensus_specs_tpu.serve import buckets

        n = self.n_validators
        precompile_key(
            buckets.slot_key(
                n,
                n // int(self._spec.SLOTS_PER_EPOCH),
                min(int(self._spec.SYNC_COMMITTEE_SIZE), n),
                self._plan,
            )
        )

    def _ensure_booted(self) -> None:
        if not self._booted:
            self.boot()

    # ---------------------------------------------------------- serving --

    @property
    def root(self) -> bytes:
        return self._root

    @property
    def epoch(self) -> int:
        return self._epoch

    def resident_arrays(self) -> list:
        """Every device array the booted world holds between slots: the
        static tree content, the columns, the justification state and
        the forest. Where they live is what says whether the world is
        resident on the accelerator."""
        import jax

        self._ensure_booted()
        return jax.tree_util.tree_leaves(
            (self._static[0], self._carry.cols, self._carry.just, self._carry.forest)
        )

    def status(self) -> dict:
        out = {
            "booted": self._booted,
            "booting": self.busy,
            "slots": self._seq,
            "epoch": self._epoch,
            "root": self._root.hex(),
            "dedup_window": len(self._applied),
            "lineage": dict(self._lineage),
        }
        if self.busy:
            out["retry_after_s"] = self.retry_after_s()
        return out

    def execute(
        self, req: SlotRequest, prep=None, mesh=None
    ) -> tuple[SlotResult, dict]:
        """Run one slot end to end and commit it. Returns the result
        plus the per-phase wall dict ({"slot.verify": ms, ...}) the
        service merges into the request waterfall. Thread-safe; slots
        serialize (they share one state), which is the pipeline's
        overlap story: the NEXT flush's host prep runs while this
        slot's device phases execute."""
        with self._lock:
            self._ensure_booted()
            hit = self._applied.get(int(req.slot))
            if hit is not None:
                obs.count("slot.replays", 1)
                return replace(hit, replayed=True), {}

            def device():
                return self._device_slot(req, prep, mesh)

            def host():
                return self._host_slot(req)

            result, carry, phases = fault.degrade("slot.reroot", device, host)
            # durable-first commit: the checkpoint (carrying the result
            # in its dedup window) lands before anything in memory moves
            # or the caller sees a verdict — a crash on either side of
            # this line loses nothing (retry re-applies or replays)
            window = OrderedDict(self._applied)
            window[int(req.slot)] = result
            while len(window) > self.dedup_cap:
                window.popitem(last=False)
            staged = (
                self._carry,
                self._seq,
                self._epoch,
                self._root,
                self._applied,
            )
            self._carry = carry
            self._seq += 1
            self._epoch = int(result.epoch)
            self._root = result.state_root
            self._applied = window
            if self.ckpt_dir:
                try:
                    self._checkpoint_locked()
                except BaseException:
                    # the durable commit failed: roll the in-memory
                    # state back so memory never outruns disk
                    (
                        self._carry,
                        self._seq,
                        self._epoch,
                        self._root,
                        self._applied,
                    ) = staged
                    self._forest_consumed = True
                    raise
            self._forest_consumed = False
            slot_pipeline.count_slot(req)
            return result, phases

    def _checkpoint_locked(self):
        from eth_consensus_specs_tpu.ops import snapshot

        return snapshot.checkpoint(
            self.ckpt_dir,
            self._carry.forest,
            self._carry.cols,
            self._carry.just,
            epoch=self._seq,
            plan=self._plan,
            state_root=self._root,
            extra={
                "slot": {
                    "epoch": int(self._epoch),
                    "applied": [_result_json(r) for r in self._applied.values()],
                }
            },
        )

    def _fresh_forest(self):
        """The forest the next donated dispatch consumes: the carry's,
        unless a failed attempt already consumed it — the deterministic
        rebuild from the (never-donated) committed columns covers a
        degrade-ladder retry after a mid-dispatch device death."""
        from eth_consensus_specs_tpu.parallel import resident

        if self._forest_consumed:
            obs.count("slot.forest_rebuilds", 1)
            forest, _ = resident.build_state_forest_device(
                self._static, self._carry.cols
            )
            return forest
        return self._carry.forest

    def _device_slot(self, req: SlotRequest, prep, mesh):
        from eth_consensus_specs_tpu.ops import snapshot
        from eth_consensus_specs_tpu.parallel import resident
        from eth_consensus_specs_tpu.parallel.resident import ResidentCarry

        fault.check("slot.verify")
        phases: dict[str, float] = {}
        t0 = time.monotonic()
        att_v, sync_v, blob_v = slot_pipeline.device_verify(req, prep, mesh=mesh)
        t1 = time.monotonic()
        phases["slot.verify"] = (t1 - t0) * 1e3
        aggs = slot_pipeline.device_aggregate(req, att_v, prep, mesh=mesh)
        t2 = time.monotonic()
        phases["slot.aggregate"] = (t2 - t1) * 1e3

        carry = self._carry
        flag_idx, reward_idx, reward_amt = slot_pipeline.plan_updates(
            req, att_v, sync_v, self.n_validators
        )
        cap_flags, cap_rewards = slot_pipeline.request_capacity(req)
        fault.check("slot.reroot")
        forest = self._fresh_forest()
        self._forest_consumed = True  # the dispatch below donates it
        new_cols, forest, root = slot_pipeline.slot_apply_device(
            self._static,
            self._plan,
            forest,
            carry.cols,
            carry.just,
            flag_idx,
            reward_idx,
            reward_amt,
            cap_flags=cap_flags,
            cap_rewards=cap_rewards,
        )
        new_just = carry.just
        epoch = self._epoch
        if req.epoch_boundary:
            warm = resident.run_epochs(
                self._spec,
                new_cols,
                new_just,
                1,
                with_root="state_inc",
                static=self._static,
                forest=forest,
            )
            new_cols, new_just, forest = warm.cols, warm.just, warm.forest
            root = snapshot.state_root_bytes(
                self._static, self._plan, forest, new_just
            )
            epoch += 1
        phases["slot.reroot"] = (time.monotonic() - t2) * 1e3
        result = SlotResult(
            slot=int(req.slot),
            att_verdicts=tuple(att_v),
            sync_verdict=bool(sync_v),
            blob_verdicts=tuple(blob_v),
            subnet_aggregates=aggs,
            state_root=root,
            epoch=epoch,
        )
        return (
            result,
            ResidentCarry(cols=new_cols, just=new_just, root_acc=None, forest=forest),
            phases,
        )

    def _host_slot(self, req: SlotRequest):
        """The degrade leg: the WHOLE slot as the sequential host fold
        from the committed (never-donated) pre-slot columns, then a
        deterministic forest rebuild for the new carry — bit-identical
        to the device pipeline by the parity gate."""
        from eth_consensus_specs_tpu.parallel import resident
        from eth_consensus_specs_tpu.parallel.resident import ResidentCarry

        t0 = time.monotonic()
        result, cols, just = slot_pipeline.host_slot_fold(
            self._spec, self._static, self._carry.cols, self._carry.just, req,
            self._epoch,
        )
        forest, _ = resident.build_state_forest_device(self._static, cols)
        phases = {"slot.reroot": (time.monotonic() - t0) * 1e3}
        return (
            result,
            ResidentCarry(cols=cols, just=just, root_acc=None, forest=forest),
            phases,
        )


# ------------------------------------------------------ warmup replay --


@lru_cache(maxsize=None)
def _warm_static(n_validators: int):
    from eth_consensus_specs_tpu.ops.state_root import synthetic_static

    return synthetic_static(slot_pipeline.slot_spec(), n_validators)


def precompile_key(key: tuple, mesh=None) -> bool:
    """Replay one ``slot_apply`` warmup key: AOT-compile the exact
    executable the live dispatch will hit (same lru_cache entry — the
    deterministic world means (meta, plan) reproduce from the key's
    registry size alone), WITHOUT touching any live forest. Returns
    False when the key's forest-plan caps don't match this build (a
    stale artifact must not poison the cache with alien shapes)."""
    import jax
    import jax.numpy as jnp

    from eth_consensus_specs_tpu.ops.state_root import (
        build_state_forest,
        forest_plan,
    )
    from eth_consensus_specs_tpu.serve import buckets

    _, n, p_flags, p_rewards, cap_val, cap_bal = (list(key) + [None] * 6)[:6]
    static = _warm_static(int(n))
    arrays, meta = static
    plan = forest_plan(meta)
    if cap_val is not None and (int(plan.cap_val), int(plan.cap_bal)) != (
        int(cap_val),
        int(cap_bal),
    ):
        obs.event(
            "serve.precompile_skipped",
            op="slot_apply",
            dims=",".join(map(str, key[1:])),
            reason="forest-plan cap mismatch",
        )
        return False
    run = slot_pipeline._compiled_slot_apply(
        meta, plan, None, int(p_flags), int(p_rewards)
    )
    cols = _warm_cols(int(n))
    just = _warm_just(int(n))
    # the donated forest as pure shape structs: AOT lower+compile warms
    # the exact executable without materializing (or consuming) a forest
    forest_sds = jax.eval_shape(
        lambda b, e, i: build_state_forest(arrays, meta, plan, b, e, i),
        cols.balance,
        cols.effective_balance,
        cols.inactivity_scores,
    )
    full_key = ("slot_apply", int(n), int(p_flags), int(p_rewards)) + (
        (int(cap_val), int(cap_bal)) if cap_val is not None else ()
    )
    with buckets.first_dispatch(*full_key):
        run.lower(
            arrays,
            forest_sds,
            cols.balance,
            cols.effective_balance,
            cols.inactivity_scores,
            cols.prev_flags,
            cols.cur_tgt_att,
            just,
            jnp.zeros(int(p_flags), jnp.int32),
            jnp.zeros(int(p_flags), jnp.uint8),
            jnp.zeros(int(p_rewards), jnp.int32),
            jnp.zeros(int(p_rewards), jnp.uint64),
        ).compile()
    return True


@lru_cache(maxsize=None)
def _warm_cols(n_validators: int):
    from eth_consensus_specs_tpu.ops.altair_epoch import example_altair_inputs

    return example_altair_inputs(n_validators)[0]


@lru_cache(maxsize=None)
def _warm_just(n_validators: int):
    from eth_consensus_specs_tpu.ops.altair_epoch import example_altair_inputs

    return example_altair_inputs(n_validators)[1]
