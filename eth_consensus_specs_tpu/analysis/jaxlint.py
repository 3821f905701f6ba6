"""jaxlint — trace-level static analysis of every registered kernel.

speclint (analysis/lint.py) reads source; the bug classes that actually
cost on accelerators live BELOW the AST, in what the tracer builds:
silent host↔device transfers, missed buffer donation, compile-key
functions that under-discriminate traced signatures (the PR 8
mesh-signature class), collectives whose axis binding only fails on a
real N-chip grid, constants baked into every executable, and dtype
drift that doubles a 32-bit kernel's footprint. jaxlint abstract-evals
every entry of the kernel registry (analysis/kernels.py) with
``jax.make_jaxpr`` — no execution, no XLA compile — and walks the
jaxprs:

``transfer-free``
    No explicit ``device_put`` (a device target or a copying transfer)
    and no host-callback primitive inside a hot traced body. Trace-time
    alias annotations (``devices=[None]``, ALIAS semantics — what
    ``jnp.asarray`` leaves behind) are exempt: they move nothing.
``donation-audit``
    Declared donate argnums are ACTUALLY donated (the jit eqn's
    ``donated_invars``) and usable (an output aval matches — XLA drops
    unusable donations silently); an undeclared input whose aval equals
    an output aval above ``ETH_SPECS_ANALYSIS_DONATE_MIN_BYTES`` is a
    missed in-place opportunity (the ROADMAP item-2 seam) unless the
    registry entry carries a reviewed waiver.
``recompile-surface``
    The registry's LIVE compile-key functions must be injective over
    the bucket grid: one key mapping to two distinct traced signatures
    means the warmup artifact lies and a "warm" boot cold-compiles (or
    worse, replays an alien mesh's shapes).
``collective-audit``
    Every ``psum``/``all_gather``/``ppermute``/... names only axes the
    enclosing shard_map mesh binds; ANY collective in a single-device
    variant is a finding (it would either fail at runtime or silently
    reduce over a one-element axis).
``constant-bloat``
    No single jaxpr constant above ``ETH_SPECS_ANALYSIS_CONST_MAX_BYTES``
    — big closure constants are re-uploaded per executable and bloat
    every compile cache entry; they belong in traced arguments (the
    fr_fft twiddle design).
``x64-drift``
    Every non-weak aval dtype is in the kernel's declared set —
    f64/i64 creeping into a kernel declared 32/uint32 (a python-int
    ``fori_loop`` bound under the x64 flag, say) silently doubles
    register pressure and memory traffic.

Findings reuse speclint's machinery: line-free fingerprints
(``kernel::rule::detail``), the ratcheting baseline
(``jaxlint_baseline.json``, ships EMPTY, ``write_baseline`` refuses
growth), registry-level ``suppress`` as the reviewed escape hatch, and
the shared CLI front end (analysis/cli.py). ``scripts/jaxlint.py`` /
``make jaxlint`` run it; CI's static-analysis job gates zero
non-baselined findings and asserts transfer-free/collective-audit are
NEVER baselined.
"""

from __future__ import annotations

import math
import os

from . import kernels as kernels_mod
from .lint import Finding

ALL_RULES = (
    "transfer-free",
    "donation-audit",
    "recompile-surface",
    "collective-audit",
    "constant-bloat",
    "x64-drift",
)

# rules whose findings may never be baselined (CI asserts this): a
# transfer or an unbound collective in a hot body is a bug, not debt
HARD_RULES = ("transfer-free", "collective-audit")

_CALLBACK_PRIMS = {
    "pure_callback",
    "io_callback",
    "debug_callback",
    "callback",
    "host_callback",
    "outside_call",
    "infeed",
    "outfeed",
}

_COLLECTIVE_PRIMS = {
    "psum",
    "psum_invariant",  # shard_map's check_vma rewrite renames psum
    "pmin",
    "pmax",
    "pmean",
    "all_gather",
    "all_to_all",
    "ppermute",
    "pgather",
    "psum_scatter",
    "reduce_scatter",
    "axis_index",
}


def const_max_bytes() -> int:
    raw = os.environ.get("ETH_SPECS_ANALYSIS_CONST_MAX_BYTES", "")
    try:
        return int(raw) if raw else 1 << 20
    except ValueError:
        return 1 << 20


def donate_min_bytes() -> int:
    raw = os.environ.get("ETH_SPECS_ANALYSIS_DONATE_MIN_BYTES", "")
    try:
        return int(raw) if raw else 1 << 20
    except ValueError:
        return 1 << 20


# --------------------------------------------------------- jaxpr walking --


def iter_eqns(jaxpr):
    """Every eqn of a (Closed)Jaxpr, recursing through sub-jaxprs in eqn
    params (pjit/shard_map/scan/while/cond bodies)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        yield eqn
        for val in eqn.params.values():
            vals = val if isinstance(val, (list, tuple)) else [val]
            for sub in vals:
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    yield from iter_eqns(sub)


def iter_consts(jaxpr):
    """(const, nbytes) for this jaxpr and every sub-jaxpr's constvals."""
    import numpy as np

    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for const in getattr(jaxpr, "consts", []) or []:
        arr = np.asarray(const)
        yield const, arr.nbytes
    for eqn in inner.eqns:
        for val in eqn.params.values():
            vals = val if isinstance(val, (list, tuple)) else [val]
            for sub in vals:
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    yield from iter_consts(sub)


def iter_avals(jaxpr):
    """Every aval bound anywhere in the jaxpr (invars, outvars, every
    eqn's vars, recursively)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for v in list(inner.invars) + list(inner.outvars):
        av = getattr(v, "aval", None)
        if av is not None:
            yield av
    for eqn in iter_eqns(jaxpr):
        for v in list(eqn.invars) + list(eqn.outvars):
            av = getattr(v, "aval", None)
            if av is not None:
                yield av


def _aval_nbytes(av) -> int:
    try:
        return int(math.prod(av.shape)) * av.dtype.itemsize
    except (AttributeError, TypeError):
        return 0


def _axis_names(eqn) -> tuple[str, ...]:
    """Axis names a collective eqn reduces/gathers over."""
    for param in ("axes", "axis_name", "axis"):
        val = eqn.params.get(param)
        if val is None:
            continue
        if isinstance(val, (list, tuple)):
            return tuple(str(a) for a in val if isinstance(a, str))
        if isinstance(val, str):
            return (str(val),)
    return ()


def trace_variant(variant):
    """Abstract-eval one registry variant into a ClosedJaxpr (no
    execution, no compile)."""
    import jax

    return jax.make_jaxpr(variant.fn, static_argnums=variant.static_argnums)(
        *variant.args
    )


# ------------------------------------------------------------------ rules --


def _f(spec, rule: str, detail: str, message: str) -> Finding:
    # path = kernel name: the fingerprint becomes kernel::rule::detail
    # (line-free, like speclint's path::rule::symbol)
    return Finding(rule, spec.name, 0, detail, message)


def rule_transfer_free(spec, variant, closed) -> list[Finding]:
    findings = []
    for eqn in iter_eqns(closed):
        name = eqn.primitive.name
        if name == "device_put":
            devices = eqn.params.get("devices", ())
            semantics = eqn.params.get("copy_semantics", ())
            explicit = any(d is not None for d in devices)
            copies = any("ALIAS" not in str(s).upper() for s in semantics)
            if explicit or copies:
                findings.append(
                    _f(
                        spec,
                        "transfer-free",
                        f"{variant.label}:device_put",
                        f"{spec.name}/{variant.label}: explicit device_put "
                        f"inside the traced body (devices={devices}, "
                        f"copy_semantics={semantics}) — a host<->device "
                        "transfer on the hot path, invisible to the span's "
                        "roofline accounting",
                    )
                )
        elif name in _CALLBACK_PRIMS:
            findings.append(
                _f(
                    spec,
                    "transfer-free",
                    f"{variant.label}:{name}",
                    f"{spec.name}/{variant.label}: host-callback primitive "
                    f"{name} inside the traced body — every dispatch "
                    "round-trips the host, serializing the accelerator",
                )
            )
    return findings


def rule_donation_audit(spec, variant, closed) -> list[Finding]:
    """Donation contract on the SINGLE-device variant (mesh variants
    shard the same buffers; donation is declared once, at the jit)."""
    if variant.mesh is not None:
        return []
    findings = []
    inner = closed.jaxpr
    in_avals = [getattr(v, "aval", None) for v in inner.invars]
    out_avals = [getattr(v, "aval", None) for v in inner.outvars]

    # what the traced callable ACTUALLY donates: the top-level jit eqn
    donated = [False] * len(in_avals)
    for eqn in inner.eqns:
        if eqn.primitive.name == "jit" and "donated_invars" in eqn.params:
            flags = eqn.params["donated_invars"]
            # map jit operands back to top-level invars
            positions = {id(v): i for i, v in enumerate(inner.invars)}
            for opv, flag in zip(eqn.invars, flags):
                i = positions.get(id(opv))
                if i is not None and flag:
                    donated[i] = True

    def key(av):
        return (tuple(av.shape), str(av.dtype)) if av is not None else None

    out_keys: dict = {}
    for av in out_avals:
        k = key(av)
        if k is not None:
            out_keys[k] = out_keys.get(k, 0) + 1

    for argnum in spec.donate:
        if argnum >= len(in_avals):
            findings.append(
                _f(
                    spec,
                    "donation-audit",
                    f"declared:arg{argnum}:missing",
                    f"{spec.name}: registry declares donate argnum {argnum} "
                    f"but the traced callable has only {len(in_avals)} flat "
                    "inputs",
                )
            )
            continue
        if not donated[argnum]:
            findings.append(
                _f(
                    spec,
                    "donation-audit",
                    f"declared:arg{argnum}:not-donated",
                    f"{spec.name}: registry declares argnum {argnum} donated "
                    "but the jit does not mark it (donated_invars) — the "
                    "declaration documents an alias the compiler never makes",
                )
            )
        elif out_keys.get(key(in_avals[argnum]), 0) <= 0:
            findings.append(
                _f(
                    spec,
                    "donation-audit",
                    f"declared:arg{argnum}:unusable",
                    f"{spec.name}: donated argnum {argnum} "
                    f"(aval {key(in_avals[argnum])}) matches no output aval — "
                    "XLA silently drops unusable donations; the buffer is "
                    "freed, not reused",
                )
            )
        else:
            out_keys[key(in_avals[argnum])] -= 1

    # missed opportunities: undeclared inputs whose aval equals a
    # remaining output aval, above the byte threshold
    if spec.donation_waiver is None:
        floor = donate_min_bytes()
        budget = dict(out_keys)
        for i, av in enumerate(in_avals):
            if av is None or donated[i] or i in spec.donate:
                continue
            k = key(av)
            if budget.get(k, 0) > 0 and _aval_nbytes(av) >= floor:
                budget[k] -= 1
                findings.append(
                    _f(
                        spec,
                        "donation-audit",
                        f"opportunity:arg{i}",
                        f"{spec.name}: input {i} (aval {k}, "
                        f"{_aval_nbytes(av)} B) matches an output aval and is "
                        "not donated — declare donate_argnums (in-place "
                        "update, halves the resident footprint) or a "
                        "donation_waiver in the kernel registry",
                    )
                )
    return findings


def rule_collective_audit(spec, variant, closed) -> list[Finding]:
    findings = []
    bound: set[str] = set()
    if variant.mesh is not None:
        bound = {str(a) for a in variant.mesh.axis_names}
    for eqn in iter_eqns(closed):
        name = eqn.primitive.name
        if name == "shard_map":
            eqn_mesh = eqn.params.get("mesh")
            if eqn_mesh is not None and variant.mesh is not None:
                eqn_axes = {str(a) for a in getattr(eqn_mesh, "axis_names", ())}
                if eqn_axes - bound:
                    findings.append(
                        _f(
                            spec,
                            "collective-audit",
                            f"{variant.label}:alien-mesh",
                            f"{spec.name}/{variant.label}: shard_map binds "
                            f"axes {sorted(eqn_axes)} but the registry's mesh "
                            f"only has {sorted(bound)} — the variant is "
                            "sharded over a mesh the serve layer never built",
                        )
                    )
            continue
        if name not in _COLLECTIVE_PRIMS:
            continue
        name = "psum" if name == "psum_invariant" else name  # canonical fingerprint
        axes = _axis_names(eqn)
        if variant.mesh is None:
            findings.append(
                _f(
                    spec,
                    "collective-audit",
                    f"{variant.label}:{name}",
                    f"{spec.name}/{variant.label}: collective {name} (axes "
                    f"{axes or '?'}) in the SINGLE-device variant — it either "
                    "fails at dispatch or silently reduces a one-element "
                    "axis; the single-device path must stay collective-free",
                )
            )
        else:
            unbound = [a for a in axes if a not in bound]
            if unbound:
                findings.append(
                    _f(
                        spec,
                        "collective-audit",
                        f"{variant.label}:{name}:{'+'.join(unbound)}",
                        f"{spec.name}/{variant.label}: collective {name} "
                        f"names axes {unbound} that the enclosing shard_map "
                        f"mesh ({sorted(bound)}) does not bind — this only "
                        "explodes on a real multi-chip grid (the mesh-smoke "
                        "class of bug)",
                    )
                )
    return findings


def rule_constant_bloat(spec, variant, closed, limit: int | None = None) -> list[Finding]:
    import numpy as np

    limit = const_max_bytes() if limit is None else limit
    findings = []
    for const, nbytes in iter_consts(closed):
        if nbytes > limit:
            arr = np.asarray(const)
            findings.append(
                _f(
                    spec,
                    "constant-bloat",
                    f"{variant.label}:const{arr.shape}",
                    f"{spec.name}/{variant.label}: {nbytes} B constant "
                    f"(shape {arr.shape}, {arr.dtype}) baked into the jaxpr "
                    f"(limit {limit} B) — closure constants ride every "
                    "executable and bloat each compile-cache entry; pass it "
                    "as a traced argument (the fr_fft twiddle pattern)",
                )
            )
    return findings


def rule_x64_drift(spec, variant, closed) -> list[Finding]:
    findings = []
    seen: set[str] = set()
    for av in iter_avals(closed):
        dt = getattr(av, "dtype", None)
        if dt is None:
            continue
        name = str(dt)
        if name in spec.dtypes or name in seen:
            continue
        # 0-d weak-typed INTEGER scalars are literal-derived trace
        # constants (python ints riding a mask or a shift) — not real
        # buffers. Float weaks get no exemption: a python float leaking
        # into a u32 kernel is a weak f64 (f32 under jax's default-dtype
        # demotion is still drift in an integer kernel), exactly the
        # class the rule exists for
        if (
            getattr(av, "ndim", None) == 0
            and getattr(av, "weak_type", False)
            and getattr(dt, "kind", None) in ("i", "u")
        ):
            continue
        seen.add(name)
        findings.append(
            _f(
                spec,
                "x64-drift",
                f"{variant.label}:{name}",
                f"{spec.name}/{variant.label}: {name} aval (shape "
                f"{tuple(getattr(av, 'shape', ()))}) outside the declared "
                f"dtype set {sorted(spec.dtypes)} — 64-bit drift in a "
                "32-bit kernel doubles register pressure and HBM traffic "
                "(python-int loop bounds under the x64 flag are the usual "
                "culprit)",
            )
        )
    return findings


def rule_recompile_surface(spec, mesh, grid=None) -> list[Finding]:
    """Injectivity of the LIVE compile-key function over the bucket
    grid: one serve/warmup key must map to exactly one traced
    signature. ``grid`` lets analyze() evaluate the key grid once."""
    if spec.key_grid is None:
        return []
    findings = []
    by_key: dict[tuple, set] = {}
    by_sig: dict[tuple, set] = {}
    for key, sig in spec.key_grid(mesh) if grid is None else grid:
        by_key.setdefault(tuple(key), set()).add(tuple(sig))
        by_sig.setdefault(tuple(sig), set()).add(tuple(key))
    for key, sigs in sorted(by_key.items()):
        if len(sigs) > 1:
            findings.append(
                _f(
                    spec,
                    "recompile-surface",
                    f"collision:{':'.join(map(str, key))}",
                    f"{spec.name}: serve key {key} maps to "
                    f"{len(sigs)} DISTINCT traced signatures "
                    f"({sorted(map(str, sigs))[:2]}...) — the warmup artifact "
                    "replays one compile where the dispatch pays several "
                    "(the PR 8 mesh-signature bug class, generalized)",
                )
            )
    for sig, keys in sorted(by_sig.items()):
        if len(keys) > 1:
            # the fingerprint embeds the colliding KEYS (not their
            # count): two unrelated aliasing groups must stay distinct
            # findings, and a baselined one must not mask a future one
            aliased = "+".join(
                ":".join(map(str, k)) for k in sorted(keys)
            )
            findings.append(
                _f(
                    spec,
                    "recompile-surface",
                    f"aliased:{aliased}",
                    f"{spec.name}: {len(keys)} distinct serve keys "
                    f"({sorted(map(str, keys))[:3]}) share ONE traced "
                    "signature — warmup replays compile the same executable "
                    "repeatedly and the compile accounting overcounts",
                )
            )
    return findings


# ------------------------------------------------------------------ engine --


def analyze(
    mesh=None,
    rules: set[str] | None = None,
    registry: tuple | None = None,
    only: set[str] | None = None,
) -> tuple[list[Finding], dict]:
    """Run the selected trace-level rules over the kernel registry.
    Returns (findings, stats). ``mesh=None`` analyzes single-device
    variants only (mesh variants need >= 2 devices); ``only`` narrows to
    a kernel-name subset (the cheap tier-1 test lane uses it)."""
    rules = set(rules) if rules is not None else set(ALL_RULES)
    registry = kernels_mod.REGISTRY if registry is None else registry
    findings: list[Finding] = []
    stats = {"kernels": 0, "variants": 0, "mesh_variants": 0, "keys": 0}
    for spec in registry:
        if only is not None and spec.name not in only:
            continue
        stats["kernels"] += 1
        for variant in spec.build_variants(mesh):
            stats["variants"] += 1
            if variant.mesh is not None:
                stats["mesh_variants"] += 1
            closed = trace_variant(variant)
            if "transfer-free" in rules:
                findings.extend(rule_transfer_free(spec, variant, closed))
            if "donation-audit" in rules:
                findings.extend(rule_donation_audit(spec, variant, closed))
            if "collective-audit" in rules:
                findings.extend(rule_collective_audit(spec, variant, closed))
            if "constant-bloat" in rules:
                findings.extend(rule_constant_bloat(spec, variant, closed))
            if "x64-drift" in rules:
                findings.extend(rule_x64_drift(spec, variant, closed))
        if "recompile-surface" in rules and spec.key_grid is not None:
            grid = spec.key_grid(mesh)
            stats["keys"] += len(grid)
            findings.extend(rule_recompile_surface(spec, mesh, grid))
        if spec.suppress:
            findings = [
                f
                for f in findings
                if not (f.path == spec.name and f.rule in spec.suppress)
            ]
    # one finding per fingerprint: several variants repeating the same
    # defect (e.g. both sha tiles) collapse, like speclint's line-free
    # fingerprints
    seen: set[str] = set()
    unique = []
    for f in sorted(findings, key=lambda f: (f.path, f.rule, f.symbol)):
        if f.fingerprint in seen:
            continue
        seen.add(f.fingerprint)
        unique.append(f)
    return unique, stats
