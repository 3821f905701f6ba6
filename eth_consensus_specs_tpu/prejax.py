"""Pre-jax-init device-count bootstrap — the ONE implementation.

XLA reads ``XLA_FLAGS`` exactly once, at backend init, so anything that
wants N virtual CPU devices must mutate the environment BEFORE the
first device query. Three call sites share this logic and had started
to grow copies:

  * ``scripts/serve_bench.py`` / ``scripts/jaxlint.py`` — pre-parse
    ``--chips`` from argv before importing anything jax-touching
    (they load this file by PATH via ``scripts/prejax.py``, so no
    package import happens before the flags are set);
  * the replica child boot (serve/replica.py) — a spawned replica owns
    a fresh interpreter whose backend has not initialized yet, but it
    INHERITS the parent's ``XLA_FLAGS`` (e.g. the bench parent's 8
    virtual devices), so its per-replica ``mesh_chips`` must
    authoritatively REPLACE the inherited device-count flag, not
    defer to it.

This module must import nothing beyond the stdlib ``os``/``sys``: the
scripts load it before jax exists in the process, and the constraint is
what makes that loading order safe.
"""

from __future__ import annotations

import os
import sys

_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"


def parse_int_flag(name: str, argv: list[str] | None = None, default: int = 0) -> int:
    """Pre-parse ``--<name> N`` / ``--<name>=N`` from argv without
    argparse (which would force importing the full CLI module graph
    before the env mutation)."""
    argv = sys.argv if argv is None else argv
    flag = f"--{name}"
    n = default
    for i, a in enumerate(argv):
        if a == flag and i + 1 < len(argv):
            try:
                n = int(argv[i + 1])
            except ValueError:
                pass
        elif a.startswith(flag + "="):
            try:
                n = int(a.split("=", 1)[1])
            except ValueError:
                pass
    return n


def parse_chips(argv: list[str] | None = None, default: int = 0) -> int:
    return parse_int_flag("chips", argv, default)


def parse_replicas(argv: list[str] | None = None, default: int = 0) -> int:
    return parse_int_flag("replicas", argv, default)


def parse_chips_matrix(argv: list[str] | None = None) -> tuple[int, ...]:
    """Pre-parse ``--chips-matrix 1,8`` — the per-replica chip cycle of
    a heterogeneous fleet (serve_bench's fleet-matrix mode)."""
    argv = sys.argv if argv is None else argv
    raw = ""
    for i, a in enumerate(argv):
        if a == "--chips-matrix" and i + 1 < len(argv):
            raw = argv[i + 1]
        elif a.startswith("--chips-matrix="):
            raw = a.split("=", 1)[1]
    try:
        return tuple(int(x) for x in raw.split(",") if x.strip())
    except ValueError:
        return ()


def chips_xla_flags(n: int, existing: str = "") -> str:
    """``XLA_FLAGS`` with the virtual-device-count flag forced to ``n``:
    any existing count flag is stripped, and ``n > 1`` appends the new
    one (``n <= 1`` means the platform default of one device)."""
    toks = [t for t in existing.split() if not t.startswith(_DEVICE_COUNT_FLAG)]
    if n > 1:
        toks.append(f"{_DEVICE_COUNT_FLAG}={n}")
    return " ".join(toks)


def replica_chips_env(n: int, environ=None) -> dict[str, str]:
    """The env assignments a spawned replica applies FIRST (before its
    backend initializes) so it owns exactly ``n`` virtual CPU devices:
    authoritative — an inherited device-count flag (the bench parent's)
    is replaced, because the replica's mesh slice is per-replica policy,
    not process-wide inheritance. Off-cpu the device count is real
    hardware and the flag is left alone (``mesh_chips`` caps the mesh
    instead)."""
    environ = os.environ if environ is None else environ
    if environ.get("JAX_PLATFORMS") != "cpu" or n <= 0:
        return {}
    return {"XLA_FLAGS": chips_xla_flags(n, environ.get("XLA_FLAGS", ""))}


def force_virtual_chips(
    default: int = 0, env_var: str | None = "ETH_SPECS_SERVE_CHIPS"
) -> int:
    """Pre-parse ``--chips N`` from argv (falling back to ``env_var``,
    then ``default``) and force that many virtual CPU devices via
    ``XLA_FLAGS`` — only on the cpu platform, only when the flag is not
    already set (an operator-set flag wins), and only for N > 1.
    The platform is the caller's to name: virtual devices exist only
    under ``JAX_PLATFORMS=cpu``, and with it unset JAX picks the
    machine's accelerator. Returns the resolved chip count."""
    n = parse_chips()
    if n <= 0 and env_var:
        try:
            n = int(os.environ.get(env_var, "0") or 0)
        except ValueError:
            n = 0
    if n <= 0:
        n = default
    flags = os.environ.get("XLA_FLAGS", "")
    if (
        n > 1
        and os.environ.get("JAX_PLATFORMS") == "cpu"
        and _DEVICE_COUNT_FLAG.lstrip("-") not in flags
    ):
        os.environ["XLA_FLAGS"] = chips_xla_flags(n, flags)
    return n
