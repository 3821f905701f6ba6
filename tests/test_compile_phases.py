"""What set-up is made of, as the program files it: the four phases of every
XLA compile by leg (obs/xprof's listener on jax.monitoring), and the spans
round the set-up verbs (register_pubkeys, the key table's two legs, a key of
precompile, a C core's load). The persistent cache is off on the CPU, so a hit is driven with
JAX's own event names through jax.monitoring's record functions."""

from __future__ import annotations

import jax
import jax.monitoring as mon
import numpy as np
import pytest

from eth_consensus_specs_tpu import obs
from eth_consensus_specs_tpu.crypto import signature
from eth_consensus_specs_tpu.obs import registry as registry_mod
from eth_consensus_specs_tpu.obs import waterfall, xprof
from eth_consensus_specs_tpu.obs.registry import Registry
from eth_consensus_specs_tpu.serve import buckets
from eth_consensus_specs_tpu.serve.config import ServeConfig
from eth_consensus_specs_tpu.serve.service import VerifyService

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
PHASES = ("trace_ms", "lower_ms", "compile_ms")

X = np.arange(7.0)  # a host array: making it compiles nothing


@pytest.fixture(autouse=True)
def _fresh_registry_and_listener(monkeypatch):
    monkeypatch.setattr(registry_mod, "_REGISTRY", Registry())
    xprof.install_compile_listener()


def hists() -> dict:
    return obs.snapshot()["histograms"]


def compile_events() -> list[dict]:
    return [e for e in obs.get_registry().events if e["kind"] == "xla.compile"]


def phase(event: str, seconds: float, fun_name: str, inside=()) -> None:
    """One phase as JAX reports it: a scalar at its entry, whatever runs
    inside it, the duration at its exit."""
    mon.record_scalar(event, 0.0, fun_name=fun_name)
    for args in inside:
        phase(*args)
    mon.record_event_duration_secs(event, seconds, fun_name=fun_name)


def cache_hit(read_s: float, compile_s: float, fun_name: str) -> None:
    """A persistent-cache hit as jax/_src/compiler.py reports it: inside the
    backend-compile phase, the hit, then the read's own duration."""
    mon.record_scalar(COMPILE, 0.0, fun_name=fun_name)
    mon.record_event(CACHE_HIT)
    mon.record_event_duration_secs(CACHE_READ, read_s)
    mon.record_event_duration_secs(COMPILE, compile_s, fun_name=fun_name)


# ---- the listener ---------------------------------------------------------------


def test_a_jit_that_calls_a_jit_files_one_trace_and_it_is_the_outers():
    seen = []

    def jax_said(name, seconds, fun_name="", **_):
        if name == TRACE:
            seen.append((fun_name, seconds * 1e3))

    @jax.jit
    def inner_of_two(v):
        return jax.numpy.sin(v) * 2.0

    def outer_of_two(v):
        return inner_of_two(v) + 0.625

    mon.register_event_duration_secs_listener(jax_said)
    try:
        jax.jit(outer_of_two)(X).block_until_ready()
    finally:
        mon.unregister_event_duration_listener(jax_said)
    # JAX timed the inner jit and each primitive inside the outer's trace
    assert {"outer_of_two", "inner_of_two", "sin"} <= {name for name, _ in seen}
    trace = hists()["xla.trace_ms.none"]
    assert trace["count"] == 1
    assert trace["sum"] == pytest.approx(dict(seen)["outer_of_two"])
    assert trace["sum"] > dict(seen)["inner_of_two"]
    (event,) = compile_events()
    assert "outer_of_two" in event["fun_name"]
    assert event["trace_ms"] == pytest.approx(trace["sum"], abs=1e-3)
    assert event["lower_ms"] == pytest.approx(hists()["xla.lower_ms.none"]["sum"], abs=1e-3)


@pytest.mark.parametrize("leg", ["t.phases", None])
def test_the_phases_of_a_fresh_function_land_under_the_open_leg(leg):
    def fresh(v):
        return v * 3.0 + (0.125 if leg else 0.375)

    if leg:
        with waterfall.leg(leg):
            jax.jit(fresh)(X).block_until_ready()
    else:
        jax.jit(fresh)(X).block_until_ready()
    filed = {name: h["count"] for name, h in hists().items() if name.startswith("xla.")}
    assert filed == {f"xla.{p}.{leg or 'none'}": 1 for p in PHASES}  # a cold compile: no read
    (event,) = compile_events()
    assert event["leg"] == (leg or "none") and event["cache_hit"] is False
    assert event["ms"] > 0 and event["trace_ms"] > 0 and event["lower_ms"] > 0
    assert event["cache_read_ms"] == 0.0 and "t_mono" in event


def test_a_cache_hit_files_its_read_and_the_compiles_event_carries_it():
    with waterfall.leg("t.warm"):
        phase(TRACE, 0.040, "warm_fn")
        phase(LOWER, 0.030, "jit(warm_fn)")
        cache_hit(0.250, 0.300, "jit(warm_fn)")
        phase(COMPILE, 0.020, "jit(cold_fn)")  # the next compile: no hit, no read
    snap = hists()
    assert snap["xla.cache_read_ms.t.warm"]["count"] == 1
    assert snap["xla.cache_read_ms.t.warm"]["sum"] == pytest.approx(250.0)
    hit, cold = compile_events()
    assert hit["cache_hit"] is True and hit["cache_read_ms"] == pytest.approx(250.0)
    assert (hit["ms"], hit["trace_ms"], hit["lower_ms"]) == pytest.approx((300.0, 40.0, 30.0))
    assert cold["cache_hit"] is False and cold["cache_read_ms"] == 0.0
    assert (cold["trace_ms"], cold["lower_ms"]) == (0.0, 0.0)  # another function's phases


def test_compile_ms_counts_every_backend_compile_hits_among_them():
    """What `fr_fft_call_compiles`, `das_fft_call_compiles` and
    `shuffle_call_compiles` count: unchanged by the three new families."""
    with waterfall.leg("t.counted"):
        cache_hit(0.250, 0.300, "jit(a)")
        jax.jit(lambda v: v * 7.0 - 0.875)(X).block_until_ready()
    compiled = hists()["xla.compile_ms.t.counted"]
    assert compiled["count"] == 2 and compiled["sum"] > 300.0
    # compiled anew: the family less the reads
    assert compiled["sum"] - hists()["xla.cache_read_ms.t.counted"]["sum"] > 50.0 - 1e-6


def test_a_trace_inside_any_phase_is_left_to_the_phase_that_spans_it():
    """A trace that closes inside a lowering (or a trace) is inside that
    duration: not filed. A lowering and a compile are filed wherever they
    close: `xla.compile_ms` counts every backend compile, as it always did."""
    phase(LOWER, 0.300, "jit(caller)", inside=[(TRACE, 0.010, "traced_while_lowering")])
    phase(TRACE, 0.500, "caller", inside=[
        (TRACE, 0.010, "eager_op"),
        (LOWER, 0.100, "jit(eager_op)"),
        (COMPILE, 0.200, "jit(eager_op)"),
    ])
    assert {n: (h["count"], round(h["sum"], 6)) for n, h in hists().items()} == {
        "xla.trace_ms.none": (1, 500.0),
        "xla.lower_ms.none": (2, 400.0),
        "xla.compile_ms.none": (1, 200.0),
    }


def test_two_installs_one_listener():
    xprof.install_compile_listener()
    xprof.install_compile_listener()
    phase(TRACE, 0.040, "once")
    phase(COMPILE, 0.020, "jit(once)")
    assert hists()["xla.trace_ms.none"]["count"] == 1  # a second listener would file a second
    assert hists()["xla.compile_ms.none"]["count"] == len(compile_events()) == 1


def test_with_obs_off_nothing_is_recorded(monkeypatch):
    monkeypatch.setenv("ETH_SPECS_OBS", "0")
    assert registry_mod.refresh_enabled() is False
    try:
        with waterfall.leg("t.off"):
            jax.jit(lambda v: v * 11.0 + 0.0625)(X).block_until_ready()
            cache_hit(0.250, 0.300, "jit(a)")
        reg = obs.get_registry()
        assert reg.histograms == {} and reg.events == [] and reg.spans == {}
    finally:
        monkeypatch.setenv("ETH_SPECS_OBS", "1")
        assert registry_mod.refresh_enabled() is True


# ---- the set-up verbs -------------------------------------------------------------


def test_register_pubkeys_leaves_its_span_and_both_key_table_legs():
    registry = [signature.sk_to_pk(0x5E7B0000 + v) for v in range(64)]
    svc = VerifyService(ServeConfig(max_batch=8, max_wait_ms=20, mesh_chips=1), name="setup")
    try:
        svc.register_pubkeys(registry)
        snap = obs.snapshot()
        assert snap["spans"]["serve.register_pubkeys"]["count"] == 1
        assert snap["spans"]["key_table.validate"]["parent"] == "serve.register_pubkeys"
        whole = snap["histograms"]["serve.setup_ms.register_pubkeys"]
        validate = snap["spans"]["key_table.validate"]
        assert whole["count"] == validate["count"] == 1
        assert whole["sum"] >= validate["total_s"] * 1e3 > 0
        assert "key_table.to_device" not in snap["spans"]  # placed at the first device sum
        table = svc._keys
        limbs = table.device_limbs()
        assert table.device_limbs() is limbs  # the second call: nothing to do, nothing filed
        snap = obs.snapshot()
        assert snap["spans"]["key_table.to_device"]["count"] == 1
        assert snap["histograms"]["serve.setup_ms.key_table.to_device"]["count"] == 1
        assert limbs[0].shape == (64, 13)
    finally:
        svc.close()


def test_precompile_files_a_keys_compile_under_the_keys_leg():
    key = ("merkle_many", 3, 11)  # no other test's shape: XLA compiles it here
    buckets.reset_for_tests()
    try:
        assert buckets.precompile([key, ("an_op_of_a_later_version", 4)]) == 1
    finally:
        buckets.reset_for_tests()
    snap = obs.snapshot()
    leg = snap["spans"]["precompile.merkle_many"]
    assert leg["count"] == 1
    under = {p: snap["histograms"][f"xla.{p}.precompile.merkle_many"] for p in PHASES}
    assert all(h["count"] >= 1 for h in under.values())
    assert sum(h["sum"] for h in under.values()) <= leg["total_s"] * 1e3
    assert snap["histograms"]["serve.compile_ms.merkle_many"]["count"] == 1  # as before
    assert {e["leg"] for e in compile_events()} == {"precompile.merkle_many"}
    assert not any("an_op_of_a_later_version" in name for name in snap["spans"])


def test_a_c_cores_load_is_a_span_that_holds_the_digest():
    from eth_consensus_specs_tpu import native

    if native.get_lib() is None:
        pytest.skip("no C compiler here")
    assert native._compile() is True  # fresh: found by its digest, nothing built
    *_, event = [e for e in obs.get_registry().events if e.get("name") == "native.load"]
    assert event["core"] == "_sha256_merkle.so" and event["s"] > 0
