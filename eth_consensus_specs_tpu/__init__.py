"""eth_consensus_specs_tpu — a TPU-native executable-spec framework for the
Ethereum proof-of-stake consensus layer.

Built from scratch against the behavior of the reference executable spec
(eth-consensus-specs); the compute hot spots (SSZ merkleization, BLS12-381,
swap-or-not shuffling, KZG/DAS field FFTs) run on TPU via JAX/XLA, everything
else is first-party Python/C++.

Layout:
  ssz/        SSZ type system: serialization, merkleization, proofs
  ops/        device kernels (JAX/XLA): sha256, shuffle, bls limb math, fft
  parallel/   mesh + sharding helpers, distributed batch primitives
  utils/      bls backend switch, hash, kzg setup tooling, merkle helpers
  config/     two-tier preset (compile-time sizes) / config (runtime) system
  forks/      per-fork spec modules (phase0, altair, ...) as a class hierarchy
  compiler/   fork-composition + markdown-spec ingestion pipeline
  test_infra/ decorator/fixture engine + dual-mode yield protocol
  gen/        reference-test vector generation (runner tree, snappy dumper)
"""

__version__ = "0.1.0"

# All spec arithmetic is uint64 with overflow-as-invalid semantics
# (reference: specs/phase0/beacon-chain.md:1339-1344); the framework is
# unusable under JAX's default 32-bit promotion, so x64 is a hard
# requirement, enabled here — at the package root, before any backend
# initializes — rather than deep inside a lazily-imported kernel module.
import jax as _jax

_jax.config.update("jax_enable_x64", True)
del _jax
