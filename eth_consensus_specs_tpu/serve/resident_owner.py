"""The serving-side owner of one durable resident world.

A replica with ``ETH_SPECS_RESIDENT_CKPT_DIR`` set owns a
device-resident validator state + merkle forest whose lifecycle is
digest-gated end to end (ops/snapshot.py):

  * **boot** — restore from the latest checkpoint under the
    ``resident.restore`` degrade ladder: a verified restore resumes at
    the checkpointed epoch; a torn/corrupt checkpoint REFUSES and falls
    back to a full host re-ingest of the deterministic world (never a
    wrong answer); no checkpoint at all is a plain cold start. The boot
    then pre-warms every kernel the serving ops dispatch (epoch chain,
    root gate, scrub) so "zero cold compiles after ready" holds for the
    resident ops too, and persists the measured restore wall so the
    NEXT boot can answer probes with an honest ``retry_after_s``.
  * **advance** — ``run_epochs_checkpointed``: interval-sized donated
    jit chunks with a durable checkpoint after each, outside the
    donated chain. The returned root is the canonical combined state
    root — the value the recovery smoke bit-compares against an
    uninterrupted control run.
  * **scrub** — on demand / on idle: K salted subtrees re-hashed
    against the resident parents; a mismatch quarantines the tree
    (rebuild internal levels from the resident leaves) and re-verifies
    the root; persistent damage (a corrupted LEAF) degrades to a full
    deterministic re-ingest + replay to the current epoch.

The world itself is synthetic but DETERMINISTIC (seeded columns +
synthetic static tree content), which is what makes "re-ingest and
replay" an honest recovery strategy: two cold boots at the same config
reproduce bit-identical state, so the only trust anchor needed across
restarts is the digest chain."""

from __future__ import annotations

import json
import os
import threading
import time

from eth_consensus_specs_tpu import fault, obs
from eth_consensus_specs_tpu.obs import flight

from .config import ServeConfig

_STATS_FILE = "restore_stats.json"
# floor/fallback restore ETA before any measured boot exists
_DEFAULT_ETA_S = 2.0


class ResidentOwner:
    """Owner of the durable resident state inside one replica."""

    def __init__(self, cfg: ServeConfig, name: str = "replica"):
        self.cfg = cfg
        self.name = name
        self.ckpt_dir = cfg.resident_ckpt_dir
        self._lock = threading.Lock()
        self._ready = threading.Event()
        self._boot_t0 = time.monotonic()
        self._eta_s = self._read_eta()
        self._boot_error: BaseException | None = None
        self._spec = None
        self._static = None
        self._plan = None
        self._carry = None
        self._epoch = 0
        self._epoch0 = 0
        self._root = b""
        self._val_root: bytes | None = None
        self._scrub_salt = 0
        self._lineage: dict = {"verdict": "restoring"}

    # ------------------------------------------------------------- boot --

    def _read_eta(self) -> float:
        try:
            with open(os.path.join(self.ckpt_dir, _STATS_FILE)) as f:
                return max(float(json.load(f).get("restore_s", 0.0)), 0.05)
        except (OSError, ValueError):
            return _DEFAULT_ETA_S

    def _persist_eta(self, seconds: float) -> None:
        try:
            os.makedirs(self.ckpt_dir, exist_ok=True)
            tmp = os.path.join(self.ckpt_dir, f"{_STATS_FILE}.__tmp{os.getpid()}")
            with open(tmp, "w") as f:
                json.dump({"restore_s": seconds}, f)
            os.replace(tmp, os.path.join(self.ckpt_dir, _STATS_FILE))
        except OSError:
            pass  # honesty stats are best-effort, never boot-fatal

    def _build_world(self):
        """The deterministic resident world: seeded columns + synthetic
        static tree content. Same config -> bit-identical state, which
        is what makes cold re-ingest a correct recovery leg."""
        import jax

        from eth_consensus_specs_tpu.ops.altair_epoch import example_altair_inputs
        from eth_consensus_specs_tpu.ops.slot_pipeline import slot_spec
        from eth_consensus_specs_tpu.ops.state_root import synthetic_static

        self._spec = slot_spec()
        cols, just = example_altair_inputs(self.cfg.resident_validators)
        self._static = synthetic_static(self._spec, self.cfg.resident_validators)
        return jax.device_put(cols), jax.device_put(just)

    def _cold_ingest(self, cols0, just0):
        from eth_consensus_specs_tpu.parallel import resident
        from eth_consensus_specs_tpu.parallel.resident import ResidentCarry

        forest, plan = resident.build_state_forest_device(self._static, cols0)
        self._plan = plan
        return ResidentCarry(cols=cols0, just=just0, root_acc=None, forest=forest), 0

    def boot(self) -> None:
        """Synchronous boot (call on the replica main thread while the
        socket listener already answers probes as restoring-busy)."""
        t0 = time.monotonic()
        try:
            self._boot_inner()
        except BaseException as exc:  # noqa: BLE001 — surfaced via status
            self._boot_error = exc
            self._lineage = {"verdict": "failed", "error": repr(exc)[:200]}
            raise
        finally:
            self._persist_eta(time.monotonic() - t0)
            flight.set_lineage(self._lineage)
            self._ready.set()

    def _boot_inner(self) -> None:
        from eth_consensus_specs_tpu.ops import snapshot
        from eth_consensus_specs_tpu.parallel import resident
        from eth_consensus_specs_tpu.parallel.resident import ResidentCarry

        cols0, just0 = self._build_world()
        plan = resident.forest_plan_for(self._static)
        verdict = "cold"
        carry = None
        epoch = 0
        manifest_digest = None

        policy = self.cfg.resident_restore
        if policy != "never":
            fell_back = []

            def do_restore():
                rs = snapshot.restore(self.ckpt_dir, static=self._static)
                if rs is not None and tuple(rs.plan)[:3] != tuple(plan)[:3]:
                    # a plan-shape drift (registry size / mesh changed
                    # under the same store) is a config change, not
                    # damage: treat as no-checkpoint, don't degrade
                    obs.event(
                        "resident.checkpoint_plan_drift",
                        stored=list(rs.plan)[:3],
                        current=list(plan)[:3],
                    )
                    return None
                return rs

            def reingest():
                fell_back.append(True)
                obs.count("resident.reingests", 1)
                return None

            if policy == "require":
                rs = do_restore()
            else:
                rs = fault.degrade("resident.restore", do_restore, reingest)
            if rs is not None:
                carry = ResidentCarry(
                    cols=rs.cols, just=rs.just, root_acc=None, forest=rs.forest
                )
                self._plan = rs.plan
                epoch = rs.epoch
                self._epoch0 = int(rs.manifest["epoch_span"][0])
                manifest_digest = rs.digest
                verdict = "restored"
            elif fell_back:
                verdict = "reingested"

        if carry is None:
            carry, epoch = self._cold_ingest(cols0, just0)
            self._epoch0 = epoch

        self._carry = carry
        self._epoch = epoch
        self._root = snapshot.state_root_bytes(
            self._static, self._plan, carry.forest, carry.just
        )
        # establish LATEST + lineage durably (all blobs reuse on a
        # restored boot — content addressing makes this near-free)
        res = snapshot.checkpoint(
            self.ckpt_dir,
            carry.forest,
            carry.cols,
            carry.just,
            epoch=epoch,
            plan=self._plan,
            state_root=self._root,
            epoch0=self._epoch0,
        )
        self._val_root = bytes.fromhex(res.manifest["trees"]["val_nodes"]["root"])
        if manifest_digest is None:
            manifest_digest = res.digest
        self._lineage = {
            "manifest": manifest_digest,
            "epoch_span": [self._epoch0, epoch],
            "verdict": verdict,
            "restore_ms": round((time.monotonic() - self._boot_t0) * 1000.0, 3),
        }
        obs.event(
            "resident.boot",
            verdict=verdict,
            epoch=epoch,
            manifest=manifest_digest[:16],
        )
        self._prewarm()

    def _prewarm(self) -> None:
        """Compile every kernel the serving ops will dispatch, on a
        throwaway COPY of the state (the epoch runner donates its
        forest): after mark_ready the resident ops never cold-compile."""
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
            max(self.cfg.resident_ckpt_interval, 1),
            with_root="state_inc",
            static=self._static,
            forest=forest_copy,
        )
        snapshot.state_root_bytes(self._static, self._plan, warm.forest, warm.just)
        snapshot.scrub_forest(
            carry.forest, k=self.cfg.resident_scrub_k, salt=self._scrub_salt
        )

    # ---------------------------------------------------------- serving --

    @property
    def busy(self) -> bool:
        return not self._ready.is_set()

    def retry_after_s(self) -> float:
        """Honest backoff for a probe that arrived mid-restore: the
        previously MEASURED restore wall minus the time already spent,
        floored — the router waits about as long as the restore really
        needs instead of blackholing or hammering."""
        elapsed = time.monotonic() - self._boot_t0
        return max(round(self._eta_s - elapsed, 3), 0.05)

    def wait_ready(self, timeout: float | None = None) -> bool:
        return self._ready.wait(timeout)

    def lineage(self) -> dict:
        return dict(self._lineage)

    def status(self) -> dict:
        out = {
            "restoring": self.busy,
            "lineage": self.lineage(),
            "epoch": self._epoch,
        }
        if self.busy:
            out["retry_after_s"] = self.retry_after_s()
        if self._root:
            out["root"] = self._root.hex()
        if self._boot_error is not None:
            out["error"] = repr(self._boot_error)[:200]
        return out

    def advance(self, n_epochs: int) -> dict:
        """Advance the resident world with durable checkpoints every
        interval; returns the canonical root of the final state."""
        from eth_consensus_specs_tpu.ops import snapshot
        from eth_consensus_specs_tpu.parallel import resident

        with self._lock:
            carry, root, epoch = resident.run_epochs_checkpointed(
                self._spec,
                self._carry.cols,
                self._carry.just,
                int(n_epochs),
                static=self._static,
                forest=self._carry.forest,
                ckpt_dir=self.ckpt_dir,
                ckpt_interval=self.cfg.resident_ckpt_interval,
                epoch0=self._epoch,
            )
            self._carry = carry
            self._epoch = epoch
            self._root = root
            found = snapshot.latest(self.ckpt_dir)
            if found is not None:
                self._val_root = bytes.fromhex(
                    found[0]["trees"]["val_nodes"]["root"]
                )
                self._lineage = {
                    **self._lineage,
                    "manifest": found[1],
                    "epoch_span": [self._epoch0, epoch],
                }
                flight.set_lineage(self._lineage)
            return {"root": root.hex(), "epoch": epoch}

    def scrub(self, k: int | None = None) -> dict:
        """One scrub pass; on mismatch: postmortem (inside scrub_forest),
        quarantine-and-rebuild, root re-verify, and a full deterministic
        re-ingest + replay when the damage survives the rebuild."""
        from eth_consensus_specs_tpu.ops import snapshot

        with self._lock:
            self._scrub_salt += 1
            rep = snapshot.scrub_forest(
                self._carry.forest,
                k=k or self.cfg.resident_scrub_k,
                salt=self._scrub_salt,
                expect_root=self._val_root,
            )
            out = {
                "checks": rep.checks,
                "mismatches": rep.mismatches,
                "bad": rep.bad,
                "epoch": self._epoch,
            }
            if not rep.mismatches:
                return out
            forest = self._carry.forest
            for tree in sorted(rep.bad):
                forest = snapshot.quarantine_rebuild(forest, tree)
            self._carry = self._carry._replace(forest=forest)
            root = snapshot.state_root_bytes(
                self._static, self._plan, forest, self._carry.just
            )
            if root == self._root:
                out["recovered"] = "rebuilt"
                return out
            # the leaves themselves are damaged: rebuilt parents are
            # consistent but wrong. Deterministic world -> re-ingest and
            # replay to the current epoch, never serve the wrong root.
            obs.count("resident.reingests", 1)
            obs.event("resident.scrub_reingest", epoch=self._epoch)
            self._replay_to(self._epoch)
            out["recovered"] = "reingested"
            return out

    def _replay_to(self, epoch: int) -> None:
        from eth_consensus_specs_tpu.ops import snapshot
        from eth_consensus_specs_tpu.parallel import resident

        cols0, just0 = self._build_world()
        carry, epoch0 = self._cold_ingest(cols0, just0)
        root = snapshot.state_root_bytes(
            self._static, self._plan, carry.forest, carry.just
        )
        if epoch > epoch0:
            carry, root, _ = resident.run_epochs_checkpointed(
                self._spec,
                carry.cols,
                carry.just,
                epoch - epoch0,
                static=self._static,
                forest=carry.forest,
                ckpt_dir=self.ckpt_dir,
                ckpt_interval=self.cfg.resident_ckpt_interval,
                epoch0=epoch0,
            )
        self._carry = carry
        self._root = root
        found = snapshot.latest(self.ckpt_dir)
        if found is not None:
            self._val_root = bytes.fromhex(found[0]["trees"]["val_nodes"]["root"])

    def checkpoint_now(self) -> dict:
        from eth_consensus_specs_tpu.ops import snapshot

        with self._lock:
            res = snapshot.checkpoint(
                self.ckpt_dir,
                self._carry.forest,
                self._carry.cols,
                self._carry.just,
                epoch=self._epoch,
                plan=self._plan,
                state_root=self._root,
                epoch0=self._epoch0,
            )
            return {
                "manifest": res.digest,
                "written": res.written,
                "reused": res.reused,
                "epoch": self._epoch,
            }
