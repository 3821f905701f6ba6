"""jaxlint — trace-level rules, kernel registry, baseline, key injectivity.

Rule mechanics run on tiny synthetic kernels (hermetic specs, no
registry); the registry tests trace only the CHEAP families in the
tier-1 lane (sha256/merkle/merkle_many/shuffle/fr_fft — sub-second
jaxprs) and leave the full 9-family sweep, whose MSM/pairing traces
cost ~10 s each, to the @slow lane and CI's static-analysis job. The
deliberate key-collision test is the acceptance criterion for the
recompile-surface rule: a key function that drops a discriminating
dimension MUST fire."""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eth_consensus_specs_tpu.analysis import jaxlint, kernels
from eth_consensus_specs_tpu.analysis.kernels import KernelSpec, Variant

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spec(name="t", dtypes=("float32", "int32", "bool"), donate=(),
          waiver="test kernel", variants=None, key_grid=None, suppress=()):
    return KernelSpec(
        name=name,
        help="synthetic",
        dtypes=frozenset(dtypes),
        donate=tuple(donate),
        donation_waiver=waiver,
        suppress=tuple(suppress),
        build_variants=(lambda mesh: variants) if variants is not None else None,
        key_grid=key_grid,
    )


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _run(spec, mesh=None, rules=None):
    findings, _ = jaxlint.analyze(mesh=mesh, rules=rules, registry=(spec,))
    return findings


# ------------------------------------------------------------ transfer-free


def test_transfer_free_flags_explicit_device_put_and_callback():
    dev = jax.devices()[0]

    def moves(x):
        return jax.device_put(x, dev) + 1

    def calls_back(x):
        return jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct(x.shape, x.dtype), x
        )

    spec = _spec(variants=[
        Variant("single", jax.jit(moves), (_sds((8,), jnp.float32),)),
        Variant("cb", jax.jit(calls_back), (_sds((8,), jnp.float32),)),
    ])
    findings = _run(spec, rules={"transfer-free"})
    details = sorted(f.symbol for f in findings)
    assert details == ["cb:pure_callback", "single:device_put"]
    assert all(f.fingerprint == f"t::transfer-free::{f.symbol}" for f in findings)


def test_transfer_free_exempts_alias_annotations():
    # jnp.asarray of a numpy constant leaves devices=[None]/ALIAS
    # device_put annotations behind — they move nothing and must pass
    const = np.arange(8, dtype=np.float32)

    def benign(x):
        return x + jnp.asarray(const)

    spec = _spec(variants=[Variant("single", jax.jit(benign), (_sds((8,), jnp.float32),))])
    assert _run(spec, rules={"transfer-free"}) == []


# ----------------------------------------------------------- donation-audit


def test_donation_audit_opportunity_waiver_and_declared():
    big = (1 << 18,)  # 1 MiB of f32 — exactly the default threshold

    def inplaceable(x):
        return x + 1

    mk = lambda fn, **kw: [Variant("single", jax.jit(fn, **kw), (_sds(big, jnp.float32),))]

    # missed opportunity, no waiver -> finding
    spec = _spec(waiver=None, variants=mk(inplaceable))
    [f] = _run(spec, rules={"donation-audit"})
    assert f.symbol == "opportunity:arg0"

    # reviewed waiver silences it
    spec = _spec(waiver="buffer reused by caller", variants=mk(inplaceable))
    assert _run(spec, rules={"donation-audit"}) == []

    # declared AND actually donated -> clean
    spec = _spec(waiver=None, donate=(0,), variants=mk(inplaceable, donate_argnums=(0,)))
    assert _run(spec, rules={"donation-audit"}) == []

    # declared in the registry but the jit does not donate -> finding
    spec = _spec(waiver=None, donate=(0,), variants=mk(inplaceable))
    [f] = _run(spec, rules={"donation-audit"})
    assert f.symbol == "declared:arg0:not-donated"


def test_donation_audit_reads_the_jit_equation():
    """The audit learns what a callable donates from its top-level ``jit``
    equation (the primitive is named ``jit``; under any other name the
    rule sees no donation anywhere). Shown on the registry's own donating
    kernel: every
    forest buffer slot_apply declares is found donated AND aliased."""
    fn = jax.jit(lambda x: x + 1, donate_argnums=(0,))
    [eqn] = jax.make_jaxpr(fn)(_sds((8,), jnp.float32)).jaxpr.eqns
    assert eqn.primitive.name == "jit" and eqn.params["donated_invars"] == (True,)

    spec = kernels.by_name()["slot_apply"]
    assert spec.donate == (6, 7, 8, 9)
    findings, _ = jaxlint.analyze(rules={"donation-audit"}, registry=(spec,))
    assert [f.symbol for f in findings if f.symbol.startswith("declared:")] == []


def test_donation_audit_unusable_donation_flagged():
    # donated input whose aval matches no output: XLA drops it silently
    def shrinks(x):
        return x[:4]

    spec = _spec(
        waiver=None, donate=(0,),
        variants=[Variant("single", jax.jit(shrinks, donate_argnums=(0,)),
                          (_sds((1 << 18,), jnp.float32),))],
    )
    [f] = _run(spec, rules={"donation-audit"})
    assert f.symbol == "declared:arg0:unusable"


# --------------------------------------------------------- collective-audit


def test_collective_audit_single_device_collective_fires():
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh1 = Mesh(np.array(jax.devices()[:1]), ("m",))
    fn = jax.jit(
        shard_map(
            lambda x: jax.lax.psum(x, "m"),
            mesh=mesh1, in_specs=P("m"), out_specs=P(),
        )
    )
    # registered as the SINGLE-device variant (mesh=None): any
    # collective is a finding
    spec = _spec(variants=[Variant("single", fn, (_sds((8,), jnp.float32),))])
    findings = _run(spec, rules={"collective-audit"})
    assert [f.symbol for f in findings] == ["single:psum"]


def test_collective_audit_unbound_axis_and_alien_mesh():
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from eth_consensus_specs_tpu.parallel.mesh_ops import serve_mesh

    serve = serve_mesh()
    if serve is None:
        pytest.skip("needs >= 2 devices (conftest forces 8 on CPU)")
    rogue = Mesh(np.array(jax.devices()[:1]), ("rogue",))
    fn = jax.jit(
        shard_map(
            lambda x: jax.lax.psum(x, "rogue"),
            mesh=rogue, in_specs=P("rogue"), out_specs=P(),
        )
    )
    # registered as a mesh variant of the SERVE mesh (dp, sp): the body
    # binds an axis the declared mesh does not have
    spec = _spec(variants=[Variant("mesh", fn, (_sds((8,), jnp.float32),), mesh=serve)])
    symbols = sorted(f.symbol for f in _run(spec, rules={"collective-audit"}))
    assert symbols == ["mesh:alien-mesh", "mesh:psum:rogue"]


# ----------------------------------------------------------- constant-bloat


def test_constant_bloat_flags_big_closure_const():
    big_const = np.zeros((64, 1024), np.float32)  # 256 KiB

    def bloated(x):
        return x + jnp.asarray(big_const)[0, :8]

    spec = _spec(variants=[Variant("single", jax.jit(bloated), (_sds((8,), jnp.float32),))])
    variant = spec.build_variants(None)[0]
    closed = jaxlint.trace_variant(variant)
    findings = jaxlint.rule_constant_bloat(spec, variant, closed, limit=1024)
    assert findings and "constant-bloat" == findings[0].rule
    assert "262144 B" in findings[0].message
    # default threshold (1 MiB) lets it pass
    assert jaxlint.rule_constant_bloat(spec, variant, closed) == []


# --------------------------------------------------------------- x64-drift


def test_x64_drift_flags_upcast_and_exempts_weak_scalars():
    def drifts(x):
        return (x.astype(jnp.float64) + 1.0).astype(jnp.float32)

    spec = _spec(dtypes=("float32",), variants=[
        Variant("single", jax.jit(drifts), (_sds((8,), jnp.float32),))
    ])
    findings = _run(spec, rules={"x64-drift"})
    assert [f.symbol for f in findings] == ["single:float64"]

    # a python-int mask rides as a 0-d WEAK i64 scalar — exempt
    def masked(x):
        return x & 0xFF

    spec = _spec(dtypes=("uint64",), variants=[
        Variant("single", jax.jit(masked), (_sds((8,), jnp.uint64),))
    ])
    assert _run(spec, rules={"x64-drift"}) == []


def test_x64_drift_weak_float_scalar_is_not_exempt():
    """The weak-scalar exemption is INTEGER-only: a python float creeping
    into an integer kernel rides as a 0-d weak f32/f64 — exactly the
    drift class the rule exists for — and must fire even though it never
    materializes as an array."""

    def drifts(flags):
        # select between two python-float literals under a traced bool:
        # the result is a 0-d WEAK float that would have slipped through
        # a blanket 0-d-weak exemption
        v = jnp.where(flags[0], 1.5, 2.5)
        return (v > jnp.float64(2.0)).astype(jnp.uint32) + flags.astype(jnp.uint32)

    spec = _spec(dtypes=("uint32", "bool"), variants=[
        Variant("single", jax.jit(drifts), (_sds((4,), jnp.bool_),))
    ])
    findings = _run(spec, rules={"x64-drift"})
    assert findings, "a 0-d weak float in an integer kernel MUST fire"
    assert all(f.symbol.startswith("single:float") for f in findings)

    # the companion negative: the same shape of kernel whose 0-d weak
    # scalar is an INTEGER (a python shift amount) stays exempt
    def int_weak(flags):
        return flags.astype(jnp.uint32) << 3

    spec = _spec(dtypes=("uint32", "bool"), variants=[
        Variant("single", jax.jit(int_weak), (_sds((4,), jnp.bool_),))
    ])
    assert _run(spec, rules={"x64-drift"}) == []


# --------------------------------------------------------- recompile-surface


def test_recompile_surface_deliberate_key_collision_fires():
    """Acceptance: a key function that drops a discriminating dimension
    (here: depth — the shape the jit cache keys on) MUST be flagged."""

    def broken_grid(mesh):
        out = []
        for depth in (4, 10):
            for n in (1, 8):
                key = ("merkle_many", max(n, 8))  # depth DROPPED from the key
                sig = (((max(n, 8), 1 << depth, 8), "uint32"), depth)
                out.append((key, sig))
        return out

    spec = _spec(key_grid=broken_grid)
    findings = jaxlint.rule_recompile_surface(spec, None)
    assert any(f.symbol.startswith("collision:") for f in findings)
    assert all(f.rule == "recompile-surface" for f in findings)


def test_recompile_surface_live_serve_keys_injective():
    """The LIVE key functions (serve/buckets.merkle_many_key,
    bls_msm_key, ops/state_root.state_root_compile_key) over the real
    bucket grids, single-device AND mesh-signed."""
    from eth_consensus_specs_tpu.parallel.mesh_ops import serve_mesh

    mesh = serve_mesh()
    by_name = kernels.by_name()
    for name in ("merkle_many", "bls_msm", "state_root"):
        findings = jaxlint.rule_recompile_surface(by_name[name], mesh)
        assert findings == [], [f.message for f in findings]


def test_mesh_signature_is_what_keeps_keys_injective():
    """Dropping the mesh signature from the live merkle key collides a
    mesh-signed bucket with the single-device one — the PR 8 bug class
    the rule exists for."""
    from eth_consensus_specs_tpu.parallel.mesh_ops import (
        mesh_signature,
        pad_to_shards,
        serve_mesh,
        shard_count,
    )
    from eth_consensus_specs_tpu.serve import buckets

    mesh = serve_mesh()
    if mesh is None:
        pytest.skip("needs >= 2 devices (conftest forces 8 on CPU)")

    def unsigned_grid(_):
        cfg = (1, 2, 4, 8, 16, 32, 64)
        out = []
        for m in (None, mesh):
            shards = shard_count(m)
            key = buckets.merkle_many_key(8, 10, cfg, mesh=m)[:3]  # sig DROPPED
            batch = pad_to_shards(key[1], shards) if m is not None else key[1]
            sig = (((batch, 1 << 10, 8), "uint32"), 10, mesh_signature(m))
            out.append((key, sig))
        return out

    spec = _spec(key_grid=unsigned_grid)
    findings = jaxlint.rule_recompile_surface(spec, mesh)
    assert any(f.symbol.startswith("collision:") for f in findings)


# ------------------------------------------------------- registry contract


def test_registry_donation_policy_is_total():
    """Every registered family declares donated argnums or a reviewed
    waiver — the 'explicit donation/transfer declarations on all kernel
    families' contract."""
    assert len(kernels.REGISTRY) >= 8
    for spec in kernels.REGISTRY:
        assert spec.donate or spec.donation_waiver, spec.name
    # mesh-ness is derived from the builders (no duplicate flag):
    # the big three + the serve bls_msm seam shard over a live mesh
    from eth_consensus_specs_tpu.parallel.mesh_ops import serve_mesh

    mesh = serve_mesh()
    if mesh is not None:
        fams = kernels.mesh_families(mesh)
        assert {"merkle_many", "g1_msm", "bls_msm", "pairing"} <= fams
    # fr_fft is the family that actually donates (the fixed finding)
    assert kernels.by_name()["fr_fft"].donate == (0,)


def test_cheap_families_analyze_clean_with_mesh_variant():
    """Tier-1 lane: the sub-second families (incl. the merkle_many mesh
    variant) are finding-free under every rule."""
    from eth_consensus_specs_tpu.parallel.mesh_ops import serve_mesh

    mesh = serve_mesh()
    findings, stats = jaxlint.analyze(
        mesh=mesh, only={"sha256", "merkle", "merkle_many", "shuffle", "fr_fft"}
    )
    assert findings == [], [f.to_dict() for f in findings]
    assert stats["kernels"] == 5
    if mesh is not None:
        assert stats["mesh_variants"] >= 1
    assert stats["keys"] > 0  # merkle_many's live grid ran


@pytest.mark.slow
def test_full_registry_clean():
    """The acceptance gate: every family (>= 8, incl. >= 3 mesh
    variants on the 8-virtual-device mesh) analyzes with ZERO findings
    against the EMPTY baseline. CI's static-analysis job runs the same
    sweep through the CLI."""
    from eth_consensus_specs_tpu.analysis import lint
    from eth_consensus_specs_tpu.parallel.mesh_ops import serve_mesh

    mesh = serve_mesh()
    findings, stats = jaxlint.analyze(mesh=mesh)
    assert findings == [], [f.to_dict() for f in findings]
    assert stats["kernels"] >= 8
    if mesh is not None:
        assert stats["mesh_variants"] >= 3
    baseline = lint.load_baseline(os.path.join(REPO_ROOT, "jaxlint_baseline.json"))
    assert baseline == {}, "jaxlint baseline must ship EMPTY"


def test_baseline_empty_and_hard_rules_never_baselined():
    with open(os.path.join(REPO_ROOT, "jaxlint_baseline.json")) as fh:
        base = json.load(fh)["findings"]
    assert base == {}, "jaxlint findings are fixed in-PR, never baselined"
    for fp in base:
        for rule in jaxlint.HARD_RULES:
            assert f"::{rule}::" not in fp


# ----------------------------------------------------- shared CLI front end


def test_speclint_and_jaxlint_share_one_front_end():
    """The two CLIs build their flag sets from analysis/cli.py — same
    destinations, same baseline/json/write-baseline contract."""
    import argparse

    from eth_consensus_specs_tpu.analysis import cli, lint

    specs, jaxs = argparse.ArgumentParser(), argparse.ArgumentParser()
    cli.add_common_args(specs, default_baseline="s.json", all_rules=lint.ALL_RULES)
    cli.add_common_args(jaxs, default_baseline="j.json", all_rules=jaxlint.ALL_RULES)
    for ap in (specs, jaxs):
        flags = {a.dest for a in ap._actions}
        assert {"json_out", "rules", "baseline", "write_baseline", "force"} <= flags
    # --update-baseline stays as a compatibility alias for speclint users
    args = specs.parse_args(["--update-baseline"])
    assert args.write_baseline

    with pytest.raises(ValueError, match="unknown rules"):
        ns = specs.parse_args(["--rules", "not-a-rule"])
        cli.parse_rules(ns, lint.ALL_RULES)


def test_cli_finish_exit_codes_and_report(tmp_path):
    from eth_consensus_specs_tpu.analysis import cli, lint

    class Args:
        json_out = str(tmp_path / "r.json")
        baseline = str(tmp_path / "b.json")
        write_baseline = False
        force = False

    f = lint.Finding("x64-drift", "merkle", 0, "single:int64", "drift")
    assert cli.finish(Args(), [f], tool="jaxlint", extra={"kernels": 1}) == 2
    report = json.loads((tmp_path / "r.json").read_text())
    assert report["tool"] == "jaxlint"
    assert report["counts_by_rule"] == {"x64-drift": 1}
    assert report["extra"] == {"kernels": 1}
    assert report["new"][0]["fingerprint"] == "merkle::x64-drift::single:int64"

    # baseline the finding -> exit 0; ratchet refuses growth -> exit 1
    Args.write_baseline = True
    assert cli.finish(Args(), [f], tool="jaxlint") == 0
    g = lint.Finding("x64-drift", "shuffle", 0, "single:int64", "drift")
    assert cli.finish(Args(), [f, g], tool="jaxlint") == 1
