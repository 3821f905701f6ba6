"""Config/preset two-tier system tables (reference analogue:
test/*/unittests/test_config_invariants.py — the reference asserts
cross-constant coherence per fork x preset; spec: presets/README.md,
configs/*.yaml)."""

import pytest

from eth_consensus_specs_tpu.config import load_config, load_preset
from eth_consensus_specs_tpu.forks import available_forks, get_spec

FORKS = available_forks()
PRESETS = ["minimal", "mainnet"]


@pytest.mark.parametrize("preset", PRESETS)
@pytest.mark.parametrize("fork", FORKS)
def test_spec_loads_every_fork_preset(fork, preset):
    spec = get_spec(fork, preset)
    assert int(spec.SLOTS_PER_EPOCH) > 0


@pytest.mark.parametrize("preset", PRESETS)
def test_epoch_containment_invariants(preset):
    spec = get_spec("phase0", preset)
    assert int(spec.EPOCHS_PER_ETH1_VOTING_PERIOD) >= 1
    assert int(spec.SLOTS_PER_HISTORICAL_ROOT) % int(spec.SLOTS_PER_EPOCH) == 0
    assert int(spec.EPOCHS_PER_HISTORICAL_VECTOR) > int(
        spec.MIN_SEED_LOOKAHEAD
    )
    assert int(spec.EPOCHS_PER_SLASHINGS_VECTOR) >= 2


@pytest.mark.parametrize("preset", PRESETS)
def test_committee_sizing_invariants(preset):
    spec = get_spec("phase0", preset)
    assert 1 <= int(spec.TARGET_COMMITTEE_SIZE) <= int(spec.MAX_VALIDATORS_PER_COMMITTEE)
    assert int(spec.MAX_COMMITTEES_PER_SLOT) >= 1
    assert int(spec.SHUFFLE_ROUND_COUNT) >= 1


@pytest.mark.parametrize("preset", PRESETS)
def test_balance_invariants(preset):
    spec = get_spec("electra", preset)
    inc = int(spec.EFFECTIVE_BALANCE_INCREMENT)
    assert int(spec.MAX_EFFECTIVE_BALANCE) % inc == 0
    assert int(spec.MAX_EFFECTIVE_BALANCE_ELECTRA) % inc == 0
    assert int(spec.MIN_ACTIVATION_BALANCE) <= int(spec.MAX_EFFECTIVE_BALANCE_ELECTRA)
    assert int(spec.config.EJECTION_BALANCE) < int(spec.MIN_ACTIVATION_BALANCE)


def test_fork_epochs_monotone_mainnet():
    cfg = load_config("mainnet")
    order = [
        "ALTAIR_FORK_EPOCH",
        "BELLATRIX_FORK_EPOCH",
        "CAPELLA_FORK_EPOCH",
        "DENEB_FORK_EPOCH",
        "ELECTRA_FORK_EPOCH",
    ]
    epochs = [int(cfg[name]) for name in order if name in cfg]
    assert epochs == sorted(epochs)


def test_fork_versions_distinct_mainnet():
    cfg = load_config("mainnet")
    versions = [
        bytes(cfg[k]) for k in cfg.keys() if k.endswith("_FORK_VERSION")
    ]
    assert len(versions) == len(set(versions))


@pytest.mark.parametrize("preset", PRESETS)
def test_blob_constants_consistent(preset):
    spec = get_spec("deneb", preset)
    assert int(spec.FIELD_ELEMENTS_PER_BLOB) == 4096
    assert int(spec.config.MAX_BLOBS_PER_BLOCK) <= int(
        spec.MAX_BLOB_COMMITMENTS_PER_BLOCK
    )


@pytest.mark.parametrize("preset", PRESETS)
def test_fulu_das_constants_consistent(preset):
    spec = get_spec("fulu", preset)
    cols = int(spec.NUMBER_OF_COLUMNS)
    groups = int(spec.config.NUMBER_OF_CUSTODY_GROUPS)
    assert cols % groups == 0
    assert int(spec.CELLS_PER_EXT_BLOB) == cols
    assert int(spec.config.SAMPLES_PER_SLOT) <= cols
    assert int(spec.config.CUSTODY_REQUIREMENT) <= groups


@pytest.mark.parametrize("preset", PRESETS)
def test_preset_loader_covers_every_fork(preset):
    for fork in FORKS:
        p = load_preset(preset, fork)
        assert "SLOTS_PER_EPOCH" in p


def test_minimal_and_mainnet_differ_where_expected():
    mi = load_preset("minimal", "phase0")
    ma = load_preset("mainnet", "phase0")
    assert int(mi["SLOTS_PER_EPOCH"]) < int(ma["SLOTS_PER_EPOCH"])
    assert int(mi["MAX_COMMITTEES_PER_SLOT"]) <= int(ma["MAX_COMMITTEES_PER_SLOT"])


@pytest.mark.parametrize("fork", FORKS)
def test_domain_constants_distinct(fork):
    spec = get_spec(fork, "minimal")
    names = [n for n in dir(spec) if n.startswith("DOMAIN_")]
    values = []
    for n in names:
        v = getattr(spec, n)
        if isinstance(v, (bytes, bytearray)) or hasattr(v, "__bytes__"):
            values.append(bytes(v))
    assert len(values) == len(set(values)), "duplicate domain separators"


def test_gloas_builder_constants_sane():
    spec = get_spec("gloas", "minimal")
    assert int(spec.BUILDER_PAYMENT_THRESHOLD_NUMERATOR) <= int(
        spec.BUILDER_PAYMENT_THRESHOLD_DENOMINATOR
    )
    assert int(spec.PTC_SIZE) >= 1


@pytest.mark.parametrize("preset", PRESETS)
def test_churn_limit_invariants(preset):
    spec = get_spec("phase0", preset)
    assert int(spec.config.MIN_PER_EPOCH_CHURN_LIMIT) >= 1
    assert int(spec.config.CHURN_LIMIT_QUOTIENT) >= 1


@pytest.mark.parametrize("preset", PRESETS)
def test_electra_churn_limits_are_increment_multiples(preset):
    spec = get_spec("electra", preset)
    inc = int(spec.EFFECTIVE_BALANCE_INCREMENT)
    assert int(spec.config.MIN_PER_EPOCH_CHURN_LIMIT_ELECTRA) % inc == 0
    assert int(spec.config.MAX_PER_EPOCH_ACTIVATION_EXIT_CHURN_LIMIT) % inc == 0


@pytest.mark.parametrize("preset", PRESETS)
def test_sync_committee_constants(preset):
    spec = get_spec("altair", preset)
    assert int(spec.SYNC_COMMITTEE_SIZE) >= 1
    assert int(spec.EPOCHS_PER_SYNC_COMMITTEE_PERIOD) >= 1


@pytest.mark.parametrize("preset", PRESETS)
def test_inactivity_and_hysteresis_quotients(preset):
    spec = get_spec("altair", preset)
    assert int(spec.config.INACTIVITY_SCORE_BIAS) >= 1
    assert int(spec.config.INACTIVITY_SCORE_RECOVERY_RATE) >= 1
    assert int(spec.HYSTERESIS_DOWNWARD_MULTIPLIER) < int(
        spec.HYSTERESIS_UPWARD_MULTIPLIER
    )


def test_intervals_and_due_bps_sane(reference_tree):
    fc = None
    from eth_consensus_specs_tpu.specc import compile_fork

    fc = compile_fork("phase0", "minimal", None, True)
    assert int(fc.ATTESTATION_DUE_BPS) < 10_000


@pytest.mark.parametrize("preset", PRESETS)
def test_whistleblower_quotients_positive(preset):
    spec = get_spec("phase0", preset)
    assert int(spec.WHISTLEBLOWER_REWARD_QUOTIENT) >= 1
    assert int(spec.PROPOSER_REWARD_QUOTIENT) >= 1


@pytest.mark.parametrize("preset", PRESETS)
def test_max_operations_per_block_positive(preset):
    spec = get_spec("phase0", preset)
    for name in (
        "MAX_ATTESTATIONS",
        "MAX_DEPOSITS",
        "MAX_PROPOSER_SLASHINGS",
        "MAX_ATTESTER_SLASHINGS",
        "MAX_VOLUNTARY_EXITS",
    ):
        assert int(getattr(spec, name)) >= 1
