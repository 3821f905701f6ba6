"""Device kernels (JAX, compiled by XLA): the TPU compute path.

Modules:
  sha256        vectorized SHA-256 compression (merkle node hashing)
  merkle        whole-subtree merkleization on device
  shuffle       swap-or-not shuffle as a whole-permutation kernel
  field         BLS12-381 base-field limb arithmetic (batched)
  state_columns columnar (struct-of-arrays) mirrors of hot state regions
"""
