"""analysis/ — speclint rules, suppressions, baseline ratchet, lockwatch.

Each rule gets a positive (finding fires) and negative (clean code
passes) fixture, lint on hermetic temp repos so the real catalogs never
leak in. The repo-wide test is the acceptance gate itself: speclint is
clean on this tree and the fork-safety / lock-order baselines are
EMPTY. The lockwatch tests drive a deliberate two-lock inversion and
cross-check live serve-lock orders against the static graph.
"""

from __future__ import annotations

import json
import os
import textwrap
import threading
import time

import pytest

from eth_consensus_specs_tpu.analysis import lint, lockwatch

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Cat:
    """Stub metric catalog: names under ok./serve. are declared."""

    def declared(self, kind: str, name: str) -> bool:
        return name.startswith(("ok.", "serve."))


def _mkrepo(tmp_path, files: dict[str, str]) -> str:
    pkg = tmp_path / lint.PACKAGE
    pkg.mkdir(parents=True, exist_ok=True)
    for rel, body in files.items():
        p = pkg / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(body))
    return str(tmp_path)


def _lint(tmp_path, files, rules, **kw):
    root = _mkrepo(tmp_path, files)
    kw.setdefault("catalog", _Cat())
    kw.setdefault("declared_env", {"ETH_SPECS_DECLARED"})
    kw.setdefault("declared_sites", {"ok.site": None})
    kw.setdefault("project_checks", False)
    return lint.run(root, rules=set(rules), **kw)


# ------------------------------------------------------------ fork-safety --


def test_fork_safety_positive_and_negative(tmp_path):
    findings = _lint(
        tmp_path,
        {
            "bad.py": """\
            import threading
            _LOCK = threading.Lock()
            """,
            "good.py": """\
            import os
            import threading
            _LOCK = threading.Lock()

            def _reinit():
                global _LOCK
                _LOCK = threading.Lock()

            os.register_at_fork(after_in_child=_reinit)
            """,
        },
        {"fork-safety"},
    )
    assert [f.symbol for f in findings] == ["_LOCK"]
    assert findings[0].path.endswith("bad.py")


def test_fork_safety_import_time_thread(tmp_path):
    findings = _lint(
        tmp_path,
        {
            "bad.py": """\
            import threading
            threading.Thread(target=print, daemon=True).start()
            """,
        },
        {"fork-safety"},
    )
    assert [f.symbol for f in findings] == ["import-time-thread"]


def test_fork_safety_hook_without_reinit_still_flagged(tmp_path):
    # a register_at_fork call that re-inits OTHER state doesn't cover
    # the lock: the rule wants the lock itself reassigned under `global`
    findings = _lint(
        tmp_path,
        {
            "bad.py": """\
            import os
            import threading
            _LOCK = threading.Lock()
            _OTHER = None

            def _reinit():
                global _OTHER
                _OTHER = None

            os.register_at_fork(after_in_child=_reinit)
            """,
        },
        {"fork-safety"},
    )
    assert [f.symbol for f in findings] == ["_LOCK"]


# ---------------------------------------------------- blocking-under-lock --


def test_blocking_under_lock_positive_and_negative(tmp_path):
    findings = _lint(
        tmp_path,
        {
            "mod.py": """\
            import time
            import threading
            _LOCK = threading.Lock()

            def bad():
                with _LOCK:
                    time.sleep(1)

            def good():
                with _LOCK:
                    x = 1
                time.sleep(1)  # outside the lock: fine
                return x

            class C:
                def __init__(self):
                    self._cond = threading.Condition()

                def wait_idiom(self):
                    with self._cond:
                        self._cond.wait()  # waiting on the HELD lock: fine

                def bad_result(self, fut):
                    with self._cond:
                        return fut.result()
            """,
        },
        {"blocking-under-lock"},
    )
    whats = sorted(f.symbol for f in findings)
    assert whats == [
        "C.bad_result:Future.result() without timeout",
        "bad:time.sleep",
    ]


# -------------------------------------------------------------- lock-order --


def test_lock_order_cycle_flagged_acyclic_clean(tmp_path):
    findings = _lint(
        tmp_path,
        {
            "cyclic.py": """\
            import threading
            _A = threading.Lock()
            _B = threading.Lock()

            def one():
                with _A:
                    with _B:
                        pass

            def other():
                with _B:
                    with _A:
                        pass
            """,
            "acyclic.py": """\
            import threading
            _X = threading.Lock()
            _Y = threading.Lock()

            def one():
                with _X:
                    with _Y:
                        pass

            def other():
                with _X:
                    with _Y:
                        pass
            """,
        },
        {"lock-order"},
    )
    assert len(findings) == 1
    assert "cyclic._A" in findings[0].symbol and "cyclic._B" in findings[0].symbol


def test_lock_order_cycle_through_call_edge(tmp_path):
    # the A->B order is direct; the B->A order only exists THROUGH a
    # call — the intra-package call-edge resolution must see it
    findings = _lint(
        tmp_path,
        {
            "mod.py": """\
            import threading
            _A = threading.Lock()
            _B = threading.Lock()

            def takes_a():
                with _A:
                    pass

            def direct():
                with _A:
                    with _B:
                        pass

            def through_call():
                with _B:
                    takes_a()
            """,
        },
        {"lock-order"},
    )
    assert len(findings) == 1


# -------------------------------------------------------------- jit-purity --


def test_jit_purity_positive_and_negative(tmp_path):
    findings = _lint(
        tmp_path,
        {
            "mod.py": """\
            import os
            import jax

            def helper(x):
                flag = os.environ.get("ETH_SPECS_DECLARED")
                return x if flag else -x

            def kernel(x):
                return helper(x) + 1

            _k = jax.jit(kernel)

            def pure(x):
                return x * 2

            _p = jax.jit(pure)

            def unjitted(x):
                return os.environ.get("ETH_SPECS_DECLARED", x)
            """,
        },
        {"jit-purity"},
    )
    # helper is flagged (reachable through kernel); unjitted is not
    assert len(findings) == 1
    assert "helper" in findings[0].symbol


def test_jit_purity_shard_map_lambda_and_nested_roots(tmp_path):
    """PR 8's sharded-kernel factories wrap lambdas and nested defs —
    bodies the module-level root scan can't reach. Positive: an impure
    helper reached only through a shard_map lambda, and an env read
    directly inside a nested wrapped def. Negative: the pure factory."""
    findings = _lint(
        tmp_path,
        {
            "mod.py": """\
            import os
            import jax
            from jax.experimental.shard_map import shard_map

            def helper(x):
                flag = os.environ.get("ETH_SPECS_DECLARED")
                return x if flag else -x

            def pure_helper(x):
                return x * 2

            def factory(mesh, spec):
                # impure helper reached ONLY through the lambda wrap site
                return shard_map(
                    lambda v: helper(v), mesh=mesh, in_specs=spec, out_specs=spec
                )

            def clean_factory(mesh, spec):
                def local(v):
                    return pure_helper(v)

                return shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec)

            def dirty_factory(mesh, spec):
                def local(v):
                    flag = os.environ.get("ETH_SPECS_DECLARED")
                    return v

                return shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec)
            """,
        },
        {"jit-purity"},
    )
    symbols = sorted(f.symbol for f in findings)
    assert symbols == ["helper:reads", "local:reads"], symbols


def test_jit_purity_shard_map_nested_sibling_calls(tmp_path):
    """A wrapped nested def calling a SIBLING nested def (the pairing
    _fold_chunk idiom) and an imported function: both resolve."""
    findings = _lint(
        tmp_path,
        {
            "impure_dep.py": """\
            import os

            def imported_impure(x):
                return os.environ.get("ETH_SPECS_DECLARED", x)
            """,
            "mod.py": """\
            from eth_consensus_specs_tpu.impure_dep import imported_impure
            from jax.experimental.shard_map import shard_map

            def factory(mesh, spec):
                def fold(v):
                    return imported_impure(v)

                def local(v):
                    return fold(v)

                return shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec)
            """,
        },
        {"jit-purity"},
    )
    assert any("imported_impure" in f.symbol for f in findings), [
        f.symbol for f in findings
    ]


# ---------------------------------------------------------- obs-discipline --


def test_obs_discipline_names_and_work_bytes(tmp_path):
    findings = _lint(
        tmp_path,
        {
            "mod.py": """\
            from eth_consensus_specs_tpu import obs

            def emits():
                obs.count("ok.declared", 1)
                obs.count("not.in_catalog", 1)
                obs.count("Bad-Grammar", 1)

            def device_spans(kernel, x, wb):
                with obs.span("ok.timed", work_bytes=wb) as sp:
                    sp.result = kernel(x)
                with obs.span("ok.untimed") as sp:
                    sp.result = kernel(x)
                with obs.span("ok.hostonly"):
                    pass
            """,
        },
        {"obs-discipline"},
    )
    symbols = sorted(f.symbol for f in findings)
    assert symbols == [
        "grammar:Bad-Grammar",
        "no-work-bytes:ok.untimed",
        "undeclared:not.in_catalog",
    ]


def test_obs_discipline_compile_ms_call_sites(tmp_path):
    """first_dispatch / observe_compile_ms call sites emit the derived
    serve.compile_ms.<op> histogram family — the PR 5 gap: the metric
    literal lives in the helper, the family key at the call site."""
    findings = _lint(
        tmp_path,
        {
            "mod.py": """\
            from eth_consensus_specs_tpu.serve import buckets
            from eth_consensus_specs_tpu.serve.buckets import first_dispatch

            def good(n):
                with buckets.first_dispatch("merkle_many", n, 10):
                    pass
                buckets.observe_compile_ms("bls_msm", 3.0)

            def bad(n):
                with first_dispatch("Rogue-Op", n):
                    pass

            def dynamic(op, n):
                with buckets.first_dispatch(op, n):  # non-literal: skipped
                    pass
            """,
        },
        {"obs-discipline"},
    )
    assert [f.symbol for f in findings] == ["grammar:serve.compile_ms.Rogue-Op"]


def test_obs_discipline_checks_a_leg_in_the_compile_helpers_own_module(tmp_path):
    """serve/buckets.py is exempt for the compile helpers it defines, not for
    the legs it opens: `leg(f"precompile.{op}")` is held to the catalog's
    family row like a leg anywhere else."""

    class _PrecompileOnly:
        def declared(self, kind, name):
            return kind != "span" or name == "precompile.*"

    findings = _lint(
        tmp_path,
        {
            "serve/__init__.py": "",
            "serve/buckets.py": """\
            from eth_consensus_specs_tpu.obs import waterfall

            def precompile(keys):
                for op in keys:
                    with waterfall.leg(f"precompile.{op}"), first_dispatch(op, 4):
                        pass
                with waterfall.leg("warmup.alien"):
                    pass
            """,
        },
        {"obs-discipline"},
        catalog=_PrecompileOnly(),
    )
    assert [f.symbol for f in findings] == ["undeclared:warmup.alien"]


def test_obs_discipline_compile_ms_undeclared(tmp_path):
    class _NoCat:
        def declared(self, kind, name):
            return False

    findings = _lint(
        tmp_path,
        {
            "mod.py": """\
            from eth_consensus_specs_tpu.serve import buckets

            def f(n):
                with buckets.first_dispatch("alien_op", n):
                    pass
            """,
        },
        {"obs-discipline"},
        catalog=_NoCat(),
    )
    assert [f.symbol for f in findings] == ["undeclared:serve.compile_ms.alien_op"]
    assert findings[0].fingerprint.endswith(
        "::obs-discipline::undeclared:serve.compile_ms.alien_op"
    )


def test_obs_discipline_leg_call_sites(tmp_path):
    """waterfall.leg(name) is a span of that name and keys two histogram
    families at the call site: a leg the catalog lacks a span row for is
    a finding, a declared one is none."""
    from eth_consensus_specs_tpu.obs import catalog

    findings = _lint(
        tmp_path,
        {
            "mod.py": """\
            from eth_consensus_specs_tpu.obs import waterfall

            def f(x):
                with waterfall.leg("kzg.brp"):
                    pass
                with waterfall.leg("kzg.no_such_leg"):
                    pass
                with waterfall.leg("Bad-Leg"):
                    pass
            """,
        },
        {"obs-discipline"},
        catalog=catalog,  # the program's own: kzg.brp is declared there
    )
    assert sorted(f.symbol for f in findings) == [
        "grammar:Bad-Leg",
        "grammar:serve.stage_ms.device.Bad-Leg",
        "grammar:xla.compile_ms.Bad-Leg",
        "undeclared:kzg.no_such_leg",
    ]


# ------------------------------------------------------------ env-registry --


def test_env_registry_undeclared_and_stale(tmp_path):
    root = _mkrepo(
        tmp_path,
        {
            "mod.py": """\
            import os
            A = os.environ.get("ETH_SPECS_DECLARED", "")
            B = os.environ.get("ETH_SPECS_MYSTERY", "")
            C = os.environ.get("JAX_PLATFORMS", "")  # non-project: exempt
            """,
        },
    )
    findings = lint.run(
        root,
        rules={"env-registry"},
        declared_env={"ETH_SPECS_DECLARED", "ETH_SPECS_NEVER_READ"},
        project_checks=True,
    )
    symbols = sorted(f.symbol for f in findings)
    assert symbols == ["ETH_SPECS_MYSTERY", "stale:ETH_SPECS_NEVER_READ"]


# ----------------------------------------------------- fault-site-registry --


def test_fault_site_registry_undeclared_and_unreferenced(tmp_path):
    root = _mkrepo(
        tmp_path,
        {
            "mod.py": """\
            from eth_consensus_specs_tpu import fault
            SITE = "mod.const_site"

            def f():
                fault.check("ok.site")
                fault.check("mod.rogue")
                fault.check(SITE)
            """,
        },
    )
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "m.md").write_text("exercises ok.site only\n")
    findings = lint.run(
        root,
        rules={"fault-site-registry"},
        declared_sites={"ok.site": None, "dead.site": None, "mod.const_site": None},
        project_checks=True,
    )
    symbols = sorted(f.symbol for f in findings)
    # rogue: undeclared literal; const_site resolved through the module
    # constant but unreferenced by docs/tests; dead.site: declared+unused
    assert symbols == [
        "mod.rogue",
        "unreferenced:dead.site",
        "unreferenced:mod.const_site",
    ]


# ------------------------------------------------------------ suppressions --


def test_suppression_comment_honored(tmp_path):
    findings = _lint(
        tmp_path,
        {
            "mod.py": """\
            import threading
            _A = threading.Lock()  # speclint: disable=fork-safety
            # speclint: disable=fork-safety
            _B = threading.Lock()
            _C = threading.Lock()
            """,
        },
        {"fork-safety"},
    )
    assert [f.symbol for f in findings] == ["_C"]


# ---------------------------------------------------------------- baseline --


def test_baseline_ratchet_only_decreases(tmp_path):
    base = tmp_path / "baseline.json"
    f1 = lint.Finding("fork-safety", "pkg/a.py", 3, "_L1", "m")
    f2 = lint.Finding("fork-safety", "pkg/b.py", 9, "_L2", "m")
    lint.write_baseline(str(base), [f1, f2], force=True)

    # shrinking is allowed and drops the fixed fingerprint
    lint.write_baseline(str(base), [f1])
    assert list(json.load(base.open())["findings"]) == [f1.fingerprint]

    # growing is refused (count may only decrease)
    with pytest.raises(ValueError, match="ratchet"):
        lint.write_baseline(str(base), [f1, f2])

    # diff: baselined findings pass, novel ones are "new", fixed ones stale
    f3 = lint.Finding("lock-order", "pkg/c.py", 1, "_A+_B", "m")
    diff = lint.baseline_diff([f3], lint.load_baseline(str(base)))
    assert [f.fingerprint for f in diff["new"]] == [f3.fingerprint]
    assert diff["stale"] == [f1.fingerprint]


# ------------------------------------------------------- repo-wide (gates) --


def test_repo_speclint_clean_and_hard_rules_unbaselined():
    """The acceptance criterion itself: zero non-baselined findings on
    this tree, with EMPTY baselines for fork-safety and lock-order."""
    findings = lint.run(REPO_ROOT, project_checks=True)
    baseline = lint.load_baseline(f"{REPO_ROOT}/speclint_baseline.json")
    diff = lint.baseline_diff(findings, baseline)
    assert not diff["new"], [f.to_dict() for f in diff["new"]]
    hard = {
        fp for fp in baseline
        if "::fork-safety::" in fp or "::lock-order::" in fp
    }
    assert not hard, f"fork-safety/lock-order must be fixed, never baselined: {hard}"


def test_env_reference_docs_in_lockstep():
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, f"{REPO_ROOT}/scripts/gen_env_docs.py", "--check"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr


def _sources(*dirs: str, suffix: str = ""):
    for d in dirs:
        for dirpath, _, files in os.walk(os.path.join(REPO_ROOT, d)):
            for f in files:
                if f.endswith(suffix) and "__pycache__" not in dirpath:
                    yield os.path.join(dirpath, f)


@pytest.mark.parametrize("name", [
    "ETH_SPECS_TPU_DEVICE_PAIRING",
    "ETH_SPECS_TPU_NO_DEVICE_PAIRING",
    "ETH_SPECS_TPU_DEVICE_H2C",
    "ETH_SPECS_BENCH_ACC_TIMEOUT",
    "ETH_SPECS_BENCH_CPU_TIMEOUT",
    "ETH_SPECS_BENCH_VERIFY_TIMEOUT",
])
def test_a_variable_of_the_old_bench_is_declared_and_named_nowhere(name):
    """The BLS path routes on `bls.use_tpu()` alone and the bench driver
    these steered is gone: neither the registry nor any source of the
    program, its scripts or its workflows names them."""
    from eth_consensus_specs_tpu import envreg

    assert name not in envreg.by_name()
    paths = [*_sources(lint.PACKAGE, "scripts", suffix=".py"), *_sources(".github")]
    assert len(paths) > 100
    named = [p for p in paths if name in open(p, encoding="utf-8").read()]
    assert not named, named


def test_the_package_imports_no_module_of_the_repository_root():
    """A layer below the drivers reaches for none of them: an installed
    package boots a slot world without the checkout's root on its path."""
    import ast

    root_modules = {"__graft_entry__", "chip_smoke", "benchmark"}
    offenders = []
    for path in _sources(lint.PACKAGE, suffix=".py"):
        for node in ast.walk(ast.parse(open(path, encoding="utf-8").read())):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                names = [node.module or ""]
            offenders += [
                f"{os.path.relpath(path, REPO_ROOT)}:{node.lineno} {n}"
                for n in names if n.split(".")[0] in root_modules
            ]
    assert not offenders, offenders


def test_validate_text_rejects_uncataloged_family():
    from eth_consensus_specs_tpu.obs import export

    rogue = (
        "# HELP made_up_family_total nope\n"
        "# TYPE made_up_family_total counter\n"
        "made_up_family_total 1\n"
    )
    with pytest.raises(ValueError, match="catalog"):
        export.validate_text(rogue)
    export.validate_text(rogue, catalog=None)  # synthetic mode still works
    # the sanctioned test scratch namespace passes the default check
    export.validate_text(
        "# HELP t_probe_total t\n# TYPE t_probe_total counter\nt_probe_total 1\n"
    )


# --------------------------------------------------------------- lockwatch --


def test_lockwatch_disabled_is_passthrough(monkeypatch):
    monkeypatch.delenv("ETH_SPECS_ANALYSIS_LOCKWATCH", raising=False)
    raw = threading.Lock()
    assert lockwatch.wrap(raw, "t.raw") is raw


def test_lockwatch_flags_deliberate_inversion(monkeypatch):
    # the injected inversion's obs counter goes to a throwaway registry:
    # CI gates lockwatch.inversions == 0 on the run-level report, and a
    # deliberate test fixture must not trip a production gate (same
    # isolation discipline as the deliberate watchdog-mismatch tests)
    from eth_consensus_specs_tpu.obs import registry as obs_registry

    monkeypatch.setattr(obs_registry, "_REGISTRY", obs_registry.Registry())
    monkeypatch.setenv("ETH_SPECS_ANALYSIS_LOCKWATCH", "1")
    lockwatch.reset()
    try:
        a = lockwatch.wrap(threading.Lock(), "t.inv_a")
        b = lockwatch.wrap(threading.Lock(), "t.inv_b")
        with a:
            with b:
                pass
        assert lockwatch.inversions() == []
        # the reverse order, from another thread (the ABBA schedule)
        def reversed_order():
            with b:
                with a:
                    pass

        t = threading.Thread(target=reversed_order)
        t.start()
        t.join(timeout=30)
        inv = lockwatch.inversions()
        assert len(inv) == 1
        assert inv[0]["edge"] == "t.inv_b -> t.inv_a"
        assert inv[0]["reverse"] == "t.inv_a -> t.inv_b"
        rep = lockwatch.report()
        assert rep["inversions"] and rep["acquisitions"] >= 4
    finally:
        lockwatch.reset()


def test_lockwatch_condition_wait_keeps_stack_truthful(monkeypatch):
    monkeypatch.setenv("ETH_SPECS_ANALYSIS_LOCKWATCH", "1")
    lockwatch.reset()
    try:
        cond = threading.Condition(lockwatch.wrap(threading.RLock(), "t.cond"))
        other = lockwatch.wrap(threading.Lock(), "t.other")
        woke = []

        def waiter():
            with cond:
                cond.wait(timeout=10)
                woke.append(True)

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.1)
        # while the waiter sleeps INSIDE cond.wait (lock released through
        # the wrapper), this thread's nesting must record cond -> other
        # without seeing the waiter's phantom hold
        with cond:
            with other:
                pass
            cond.notify_all()
        t.join(timeout=10)
        assert woke == [True]
        assert ("t.cond", "t.other") in lockwatch.edges()
        assert lockwatch.inversions() == []
    finally:
        lockwatch.reset()


def test_static_and_runtime_lock_graphs_agree_on_serve(monkeypatch, bls_items):
    """Drive a real VerifyService exchange under the watchdog; every
    live acquisition order must be consistent with the static graph —
    their union stays acyclic — and zero inversions are observed."""
    from eth_consensus_specs_tpu import serve
    from eth_consensus_specs_tpu.serve.config import ServeConfig

    monkeypatch.setenv("ETH_SPECS_ANALYSIS_LOCKWATCH", "1")
    lockwatch.reset()
    try:
        svc = serve.VerifyService(ServeConfig.from_env(max_batch=2, max_wait_ms=2))
        futs = [svc.submit_bls_aggregate(*it) for it in bls_items[:4]]
        results = [f.result(timeout=120) for f in futs]
        svc.close()
        assert len(results) == 4
        assert lockwatch.acquisitions() > 0, "the watchdog saw no lock traffic"
        assert lockwatch.inversions() == []
        static = lint.build_lock_graph(lint.collect_modules(REPO_ROOT))
        agreement = lockwatch.check_against_static(static["edges"])
        assert agreement["ok"], agreement
        # the service's instance locks must appear under the SAME
        # identities the static analysis derives
        live_locks = {lk for edge in lockwatch.edges() for lk in edge}
        assert live_locks <= static["locks"] | live_locks  # names well-formed
        for lk in live_locks:
            assert lk in static["locks"], f"runtime lock {lk} unknown to statics"
    finally:
        lockwatch.reset()


@pytest.fixture(scope="module")
def bls_items():
    from eth_consensus_specs_tpu.utils import bls

    sks = [1, 2, 3]
    pks = [bls.SkToPk(sk) for sk in sks]
    items = []
    for i in range(4):
        m = bytes([i + 1]) * 32
        sig = bls.Aggregate([bls.Sign(sk, m) for sk in sks])
        items.append((pks, m, sig))
    return items
