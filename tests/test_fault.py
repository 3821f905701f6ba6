"""Fault subsystem: spec grammar, deterministic injection windows,
retry backoff, and the device->host degradation guard's bit-exactness
(ops/state_root.py and ops/block_epoch.py falling back to their host
oracles under injected device failure)."""

import numpy as np
import pytest

from eth_consensus_specs_tpu import fault, obs
from eth_consensus_specs_tpu.fault import FaultInjected


# ------------------------------------------------------------- grammar --


def test_parse_grammar_defaults_and_keys():
    rules = fault.parse(
        "gen.case:raise; state_root.*:stall:nth=3:times=2:delay=0.5;"
        "gen.dump_bytes:corrupt:times=inf"
    )
    assert [r.mode for r in rules] == ["raise", "stall", "corrupt"]
    assert (rules[0].nth, rules[0].times) == (1, 1)
    assert (rules[1].nth, rules[1].times, rules[1].delay) == (3, 2, 0.5)
    assert rules[2].times == float("inf")
    assert rules[1].matches("state_root.device")
    assert not rules[1].matches("block_epoch.device")


@pytest.mark.parametrize(
    "bad",
    ["nosite", "site:explode", "site:raise:nth", "site:raise:widget=1", ":raise"],
)
def test_parse_rejects_malformed(bad):
    with pytest.raises(ValueError):
        fault.parse(bad)


def test_check_fires_in_window_only():
    with fault.injected("probe.site:raise:nth=2:times=2"):
        fault.check("probe.site")  # hit 1: before window
        with pytest.raises(FaultInjected):
            fault.check("probe.site")  # hit 2
        with pytest.raises(FaultInjected):
            fault.check("probe.site")  # hit 3
        fault.check("probe.site")  # hit 4: window exhausted
        fault.check("other.site")  # never matches
    fault.check("probe.site")  # rules restored: no-op


def test_latch_fires_once_across_rules(tmp_path):
    latch = str(tmp_path / "latch")
    with fault.injected(f"a.site:raise:times=inf:latch={latch}"):
        with pytest.raises(FaultInjected):
            fault.check("a.site")
        fault.check("a.site")  # latch already taken: silent


def test_corrupt_flips_one_byte_then_restores():
    data = bytes(range(32))
    with fault.injected("bytes.site:corrupt"):
        mutated = fault.corrupt("bytes.site", data)
        assert mutated != data and len(mutated) == len(data)
        assert sum(a != b for a, b in zip(mutated, data)) == 1
        assert fault.corrupt("bytes.site", data) == data  # window exhausted
    assert fault.corrupt("bytes.site", data) == data


# --------------------------------------------------------------- retry --


def test_retrying_recovers_and_counts():
    calls = {"n": 0}
    slept = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    before = obs.snapshot()["counters"].get("fault.retries", 0)
    out = fault.retrying(flaky, name="t", attempts=4, retry_on=OSError, sleep=slept.append)
    assert out == "ok" and calls["n"] == 3
    assert len(slept) == 2
    assert obs.snapshot()["counters"]["fault.retries"] - before == 2


def test_retrying_exhausts_and_respects_filter():
    def always():
        raise OSError("nope")

    with pytest.raises(OSError):
        fault.retrying(always, attempts=3, retry_on=OSError, sleep=lambda _s: None)

    calls = {"n": 0}

    def wrong_kind():
        calls["n"] += 1
        raise ValueError("logic bug")

    with pytest.raises(ValueError):
        fault.retrying(wrong_kind, attempts=5, retry_on=OSError, sleep=lambda _s: None)
    assert calls["n"] == 1  # non-matching error: no retry


def test_backoff_deterministic_capped_jittered():
    a = fault.backoff_delays("site-a", 6, base_delay=0.1, max_delay=0.8, jitter=0.5)
    assert a == fault.backoff_delays("site-a", 6, base_delay=0.1, max_delay=0.8, jitter=0.5)
    b = fault.backoff_delays("site-b", 6, base_delay=0.1, max_delay=0.8, jitter=0.5)
    assert a != b  # name de-syncs concurrent retriers
    for i, d in enumerate(a):
        lo = min(0.1 * 2**i, 0.8)
        assert lo <= d <= lo * 1.5


# ------------------------------------------------------------- degrade --


def test_degrade_falls_back_on_device_failure_only():
    before = obs.snapshot()["counters"].get("fault.degraded", 0)

    def dead_device():
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating 1GB")

    assert fault.degrade("probe.degrade", dead_device, lambda: "host") == "host"
    assert obs.snapshot()["counters"]["fault.degraded"] - before == 1

    def logic_bug():
        raise KeyError("not a device failure")

    with pytest.raises(KeyError):
        fault.degrade("probe.degrade", logic_bug, lambda: "host")


def test_degrade_retry_recovers_transient_device_failure():
    # one-shot injection: the retry leg succeeds, NO degradation happens
    with fault.injected("probe.transient:raise:nth=1:times=1"):
        before = obs.snapshot()["counters"].get("fault.degraded", 0)

        def device():
            fault.check("probe.transient")
            return "device"

        assert fault.degrade("probe.transient", device, lambda: "host") == "device"
        assert obs.snapshot()["counters"].get("fault.degraded", 0) == before


def test_is_device_failure_classification():
    assert fault.is_device_failure(FaultInjected("x"))
    assert fault.is_device_failure(MemoryError())
    assert fault.is_device_failure(RuntimeError("INTERNAL: failed to allocate 1GB"))
    assert not fault.is_device_failure(ValueError("shape mismatch"))
    assert not fault.is_device_failure(AssertionError("spec violated"))


class XlaRuntimeError(RuntimeError):
    """Stand-in with the runtime's type name (classification reads it)."""


@pytest.mark.parametrize(
    "exc,degradable",
    [
        (XlaRuntimeError("UNAVAILABLE: TPU worker lost"), True),
        (XlaRuntimeError("RESOURCE_EXHAUSTED: out of memory allocating 2GB"), True),
        (XlaRuntimeError("INTERNAL: Mosaic failed to compile TPU kernel"), False),
        (XlaRuntimeError("RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. "
                         "Ran out of memory in memory space hbm"), False),
        (XlaRuntimeError("UNIMPLEMENTED: 64-bit dot is not supported"), False),
        (XlaRuntimeError("INVALID_ARGUMENT: shapes do not match"), False),
        (RuntimeError("compilation failure: kernel too large"), False),
    ],
)
def test_compile_refusal_is_not_a_device_death(exc, degradable):
    """A compiler that refuses a kernel is not a device dying under load:
    the error reaches the caller instead of the host oracle answering."""
    assert fault.is_device_failure(exc) is degradable
    if not degradable:
        with pytest.raises(type(exc)):
            fault.degrade("probe.compile", lambda: (_ for _ in ()).throw(exc), lambda: "host")


# ------------------------------------------------ multihost guards --


def test_multihost_init_failure_leaves_breadcrumb(monkeypatch):
    import jax

    from eth_consensus_specs_tpu.parallel import multihost

    def boom():
        raise RuntimeError("coordinator unreachable")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax.distributed, "initialize", boom)
    before = obs.snapshot()["counters"].get("multihost.init_failures", 0)
    assert multihost._initialize_distributed(None, None, None) is False
    assert obs.snapshot()["counters"]["multihost.init_failures"] - before == 1
    events = [e for e in obs.get_registry().events if e.get("kind") == "multihost.init_failed"]
    assert events and "coordinator unreachable" in events[-1]["error"]


def test_host_local_slice_empty_process_owns_nothing(monkeypatch):
    import jax

    from eth_consensus_specs_tpu.parallel import make_mesh, multihost

    mesh = make_mesh()
    # a process owning no devices of the mesh gets an empty block, not a
    # min()-over-empty-set ValueError
    monkeypatch.setattr(jax, "process_index", lambda: 10**9)
    assert multihost.host_local_slice(mesh, 1024) == (0, 0)


# ----------------------------------------- kernel degradation parity --


def _mk_just(rng):
    import jax.numpy as jnp

    from eth_consensus_specs_tpu.ops.state_columns import JustificationState

    def root():
        return jnp.asarray(rng.integers(0, 256, 32, dtype=np.int64).astype(np.uint8))

    return JustificationState(
        current_epoch=jnp.uint64(5),
        justification_bits=jnp.asarray([True, False, True, False]),
        prev_justified_epoch=jnp.uint64(3),
        prev_justified_root=root(),
        cur_justified_epoch=jnp.uint64(4),
        cur_justified_root=root(),
        finalized_epoch=jnp.uint64(2),
        finalized_root=root(),
        block_root_prev=root(),
        block_root_cur=root(),
        slashings_sum=jnp.uint64(0),
    )


@pytest.mark.slow  # the eager device tree at n=32 is ~1 min on CPU (same
# lane as test_state_root_device.py); block_epoch parity below covers the
# degrade machinery in tier-1
def test_state_root_degrades_bit_exact():
    import jax.numpy as jnp

    from eth_consensus_specs_tpu.forks import get_spec
    from eth_consensus_specs_tpu.ops.state_root import post_epoch_state_root, synthetic_static

    spec = get_spec("altair", "minimal")
    n = 32
    arrays, meta = synthetic_static(spec, n, seed=3)
    rng = np.random.default_rng(0)
    bal = jnp.asarray(rng.integers(1, 2**40, n, dtype=np.int64).astype(np.uint64))
    eff = jnp.asarray(rng.integers(1, 32, n, dtype=np.int64).astype(np.uint64) * 10**9)
    scores = jnp.asarray(rng.integers(0, 100, n, dtype=np.int64).astype(np.uint64))
    just = _mk_just(np.random.default_rng(1))
    clean = np.asarray(post_epoch_state_root(arrays, meta, bal, eff, scores, just))
    before = obs.snapshot()["counters"].get("fault.degraded.state_root.device", 0)
    with fault.injected("state_root.device:raise:times=inf"):
        degraded = np.asarray(post_epoch_state_root(arrays, meta, bal, eff, scores, just))
    assert (clean == degraded).all()
    after = obs.snapshot()["counters"]["fault.degraded.state_root.device"]
    assert after - before == 1


@pytest.mark.slow  # make_root_ctx's eager device trees are ~1 min on CPU
def test_block_epoch_degraded_slot_roots_bit_exact():
    """The degraded path's per-slot root chain (block_epoch_host.
    slot_root_fn_from_ctx) must xor-chain to the device kernel's acc."""
    import jax.numpy as jnp

    from eth_consensus_specs_tpu.forks import get_spec
    from eth_consensus_specs_tpu.ops import block_epoch as be
    from eth_consensus_specs_tpu.ops.state_root import synthetic_static

    spec = get_spec("capella", "minimal")
    n = 64
    cols, st0, static = be.synthetic_block_columns(spec, n, seed=1, atts_per_slot=4)
    arrays, meta = synthetic_static(spec, n, seed=2)
    rng = np.random.default_rng(3)
    scores = jnp.asarray(rng.integers(0, 100, n, dtype=np.int64).astype(np.uint64))
    just = _mk_just(np.random.default_rng(4))
    params = be.BlockEpochParams.from_spec(spec)
    ctx = be.make_root_ctx(spec, arrays, meta, static, scores, just)
    _st_c, acc_c = be.block_epoch_chain(params, n, st0, cols, static, root_ctx=ctx)
    with fault.injected("block_epoch.device:raise:times=inf"):
        _st_h, acc_h = be.block_epoch_chain(params, n, st0, cols, static, root_ctx=ctx)
    assert np.asarray(acc_c).any()  # non-trivial root chain
    assert (np.asarray(acc_c) == np.asarray(acc_h)).all()


def test_block_epoch_degrades_bit_exact():
    from eth_consensus_specs_tpu.forks import get_spec
    from eth_consensus_specs_tpu.ops import block_epoch as be

    spec = get_spec("capella", "minimal")
    n = 64
    cols, st0, static = be.synthetic_block_columns(spec, n, seed=0, atts_per_slot=4)
    params = be.BlockEpochParams.from_spec(spec)
    st_c, _acc_c = be.block_epoch_chain(params, n, st0, cols, static)
    before = obs.snapshot()["counters"].get("fault.degraded.block_epoch.device", 0)
    with fault.injected("block_epoch.device:raise:times=inf"):
        st_h, _acc_h = be.block_epoch_chain(params, n, st0, cols, static)
    assert (np.asarray(st_c.balance) == np.asarray(st_h.balance)).all()
    assert (np.asarray(st_c.cur_part) == np.asarray(st_h.cur_part)).all()
    assert (np.asarray(st_c.prev_part) == np.asarray(st_h.prev_part)).all()
    assert int(st_c.next_wd_index) == int(st_h.next_wd_index)
    assert int(st_c.next_wd_validator) == int(st_h.next_wd_validator)
    assert obs.snapshot()["counters"]["fault.degraded.block_epoch.device"] - before == 1
