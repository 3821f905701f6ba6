"""ctypes loader for the native runtime (sha256_merkle.c).

Compiles the shared object on first use with the system C compiler into
the package directory (a one-time ~1s cost), mirroring how the reference
leans on prebuilt C cores (hashlib/milagro) behind Python bindings. Set
``ETH_SPECS_TPU_NO_NATIVE=1`` to force the pure-Python fallbacks; all
callers degrade gracefully when no compiler is available."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys as _sys
import sysconfig

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "sha256_merkle.c")
_LIB = os.path.join(_DIR, "_sha256_merkle.so")

_lib: ctypes.CDLL | None = None
_tried = False


def _src_digest(*srcs: str) -> str:
    h = hashlib.sha256()
    for s in srcs:
        with open(s, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _cpu_isa_token() -> str:
    """Coarse CPU-capability fingerprint for the build stamp (x86 ISA
    extensions the optimized builds may use; empty off-x86/Linux)."""
    try:
        with open("/proc/cpuinfo") as f:
            flags = ""
            for line in f:
                if line.startswith("flags"):
                    flags = line
                    break
        return "+".join(t for t in ("bmi2", "adx") if f" {t}" in flags)
    except OSError:
        return "unknown"


def _probe_ok(lib_path: str, symbol: str) -> bool:
    """Run ``symbol()`` from the candidate library in a THROWAWAY child
    process and require exit 0.  An ISA-extension build on a CPU without
    those opcodes dies with SIGILL — isolating the first call keeps the
    crash out of the importing process and lets the flag ladder fall back
    to the portable build."""
    code = (
        "import ctypes,sys;"
        f"sys.exit(0 if ctypes.CDLL({lib_path!r}).{symbol}() == 0 else 1)"
    )
    try:
        res = subprocess.run(
            [_sys.executable, "-c", code], capture_output=True, timeout=60
        )
        return res.returncode == 0
    except (OSError, subprocess.SubprocessError):
        return False


def _ensure_shared(
    out: str,
    srcs: tuple[str, ...],
    opt: str,
    timeout: int,
    probe_symbol: str | None = None,
) -> bool:
    """Compile ``srcs[0]`` into ``out`` unless an object built from exactly
    these sources already exists. Freshness is a content-hash stamp file
    (``out + '.sha256'``), not mtimes: git does not preserve mtimes, so a
    stale or foreign binary must never silently win over the audited source
    for consensus-critical code. Links to a per-process temp name, then
    atomically renames: concurrent first-use compilations (pytest-xdist,
    parallel imports) must never let a reader dlopen a partial object."""
    # here, not at the top: obs pulls in the package, whose import may be
    # what asked for this core
    from eth_consensus_specs_tpu import obs

    # a fresh object costs the digest, a stale one `cc` for seconds
    with obs.span("native.load", core=os.path.basename(out)):
        # The stamp encodes source content AND the build variant AND the CPU
        # capability the variant relies on: a checkout (or baked image) moved
        # to a CPU without BMI2/ADX must MISS the stamp, re-enter the flag
        # ladder, and let the crash-isolated probe reject the ISA build —
        # never dlopen a mulx/adcx object into the importing process blind.
        want = f"{_src_digest(*srcs)}:{opt}:{_cpu_isa_token()}"
        stamp = out + ".sha256"
        try:
            with open(stamp) as f:
                if f.read().strip() == want and os.path.exists(out):
                    return True
        except OSError:
            pass
        cc = os.environ.get("CC") or sysconfig.get_config_var("CC") or "cc"
        tmp = f"{out}.{os.getpid()}.tmp"
        built = False
        candidates = [opt.split(), [opt.split()[0]]]
        if candidates[1] == candidates[0]:
            candidates.pop()  # single-flag opt: no distinct fallback to try
        for flags in candidates:
            # first choice may carry ISA-extension flags (BMI2/ADX measurably
            # speed the Montgomery carry chains); retry with the bare -O level
            # for compilers that reject them or CPUs that trap on the opcodes
            # (the probe below catches the latter in a crash-isolated child)
            cmd = cc.split() + flags + ["-fPIC", "-shared", "-o", tmp, srcs[0]]
            try:
                subprocess.run(cmd, check=True, capture_output=True, timeout=timeout)
            except (OSError, subprocess.SubprocessError):
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                continue
            if probe_symbol is not None and not _probe_ok(tmp, probe_symbol):
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                continue
            os.replace(tmp, out)
            built = True
            break
        if not built:
            return False
        # Stamp failure must not discard a successfully installed library —
        # worst case the next process recompiles once more.
        try:
            stamp_tmp = f"{stamp}.{os.getpid()}.tmp"
            with open(stamp_tmp, "w") as f:
                f.write(want)
            os.replace(stamp_tmp, stamp)
        except OSError:
            pass
        return True


def _compile() -> bool:
    return _ensure_shared(_LIB, (_SRC,), "-O2", 120)


def get_lib() -> ctypes.CDLL | None:
    """The loaded native library, or None when unavailable/disabled."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    if os.environ.get("ETH_SPECS_TPU_NO_NATIVE"):
        return None
    if not _compile():
        return None
    try:
        lib = ctypes.CDLL(_LIB)
    except OSError:
        return None
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.sha256_pair.argtypes = [u8p, u8p]
    lib.sha256_pairs.argtypes = [u8p, u8p, ctypes.c_uint64]
    lib.merkle_level.argtypes = [u8p, u8p, ctypes.c_uint64]
    lib.deposit_tree_insert.argtypes = [u8p, ctypes.c_uint64, u8p, ctypes.c_uint32]
    lib.deposit_tree_root.argtypes = [u8p, u8p, ctypes.c_uint64, ctypes.c_uint32, u8p]
    _lib = lib
    return _lib


def available() -> bool:
    return get_lib() is not None


def _buf(data: bytes):
    return (ctypes.c_uint8 * len(data)).from_buffer_copy(data)


def sha256_pair(data64: bytes) -> bytes:
    lib = get_lib()
    assert lib is not None and len(data64) == 64
    out = (ctypes.c_uint8 * 32)()
    lib.sha256_pair(_buf(data64), out)
    return bytes(out)


def sha256_pairs(data: bytes) -> bytes:
    """Concatenated 64-byte messages -> concatenated 32-byte digests."""
    lib = get_lib()
    assert lib is not None and len(data) % 64 == 0
    n = len(data) // 64
    out = (ctypes.c_uint8 * (32 * n))()
    lib.sha256_pairs(_buf(data), out, n)
    return bytes(out)


# --- BLS12-381 native core (bls12_381.c) -----------------------------------

_BLS_SRC = os.path.join(_DIR, "bls12_381.c")
_BLS_LIB_PATH = os.path.join(_DIR, "_bls12_381.so")

_bls_lib: ctypes.CDLL | None = None
_bls_tried = False


def _compile_bls() -> bool:
    hdr = os.path.join(_DIR, "bls12_381_consts.h")
    return _ensure_shared(
        _BLS_LIB_PATH,
        (_BLS_SRC, hdr),
        "-O3 -mbmi2 -madx -mtune=skylake-avx512",
        300,
        probe_symbol="bls_selftest",
    )


def get_bls_lib() -> ctypes.CDLL | None:
    """The native BLS12-381 library, or None when unavailable/disabled."""
    global _bls_lib, _bls_tried
    if _bls_lib is not None or _bls_tried:
        return _bls_lib
    _bls_tried = True
    if os.environ.get("ETH_SPECS_TPU_NO_NATIVE"):
        return None
    if not _compile_bls():
        return None
    try:
        lib = ctypes.CDLL(_BLS_LIB_PATH)
    except OSError:
        return None
    u8p = ctypes.POINTER(ctypes.c_uint8)
    c = ctypes
    lib.bls_selftest.restype = c.c_int
    lib.bls_g1_mul.argtypes = [u8p, c.c_uint8, u8p, u8p, u8p]
    lib.bls_g2_mul.argtypes = [u8p, c.c_uint8, u8p, u8p, u8p]
    lib.bls_g1_mul_wide.argtypes = [u8p, c.c_uint8, u8p, c.c_uint64, u8p, u8p]
    lib.bls_g2_mul_wide.argtypes = [u8p, c.c_uint8, u8p, c.c_uint64, u8p, u8p]
    lib.bls_g1_aggregate.argtypes = [c.c_uint64, u8p, u8p, u8p, u8p]
    lib.bls_g2_aggregate.argtypes = [c.c_uint64, u8p, u8p, u8p, u8p]
    lib.bls_g1_msm.argtypes = [c.c_uint64, u8p, u8p, u8p, u8p, u8p]
    lib.bls_g2_msm.argtypes = [c.c_uint64, u8p, u8p, u8p, u8p, u8p]
    lib.bls_g1_in_subgroup.argtypes = [u8p]
    lib.bls_g1_in_subgroup.restype = c.c_int
    lib.bls_g2_in_subgroup.argtypes = [u8p]
    lib.bls_g2_in_subgroup.restype = c.c_int
    lib.bls_g1_key_validate_many.argtypes = [c.c_uint64, c.c_void_p, c.c_void_p]
    lib.bls_g1_key_validate_many.restype = c.c_uint64
    lib.bls_g1_decompress_many.argtypes = [c.c_uint64, c.c_void_p, c.c_void_p, c.c_void_p]
    lib.bls_g1_decompress_many.restype = None
    lib.bls_g2_clear_cofactor.argtypes = [u8p, u8p, u8p]
    lib.bls_g2_decompress.argtypes = [u8p, u8p, u8p]
    lib.bls_g2_decompress.restype = c.c_int
    lib.bls_g2_map_set_params.argtypes = [u8p]
    lib.bls_g2_map_from_fields.argtypes = [u8p, u8p, u8p]
    lib.bls_g2_map_from_fields.restype = c.c_int
    lib.bls_g1_on_curve.argtypes = [u8p]
    lib.bls_g1_on_curve.restype = c.c_int
    lib.bls_g2_on_curve.argtypes = [u8p]
    lib.bls_g2_on_curve.restype = c.c_int
    lib.bls_pairing_check.argtypes = [c.c_uint64, u8p, u8p, u8p]
    lib.bls_pairing_check.restype = c.c_int
    lib.bls_g2_prepare_many.argtypes = [c.c_uint64, u8p, c.POINTER(c.c_uint64)]
    lib.bls_g2_prepare_many.restype = c.c_uint64
    lib.bls_pairing.argtypes = [u8p, u8p, u8p]
    lib.bls_fp_sqrt.argtypes = [u8p, u8p]
    lib.bls_fp_sqrt.restype = c.c_int
    lib.bls_fp2_sqrt.argtypes = [u8p, u8p]
    lib.bls_fp2_sqrt.restype = c.c_int
    lib.bls_fp_inv.argtypes = [u8p, u8p]
    lib.bls_fp_inv.restype = c.c_int
    lib.bls_fp2_inv.argtypes = [u8p, u8p]
    lib.bls_fp2_inv.restype = c.c_int
    if lib.bls_selftest() != 0:
        return None
    _bls_lib = lib
    return _bls_lib
