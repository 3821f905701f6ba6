"""Postmortem bundle inspector: pretty-print and diff flight-recorder
dumps (obs/flight.py).

A bundle is one JSON file written into ``ETH_SPECS_OBS_POSTMORTEM_DIR``
when a trigger fired (watchdog divergence, fault.degrade fallback, SLO
breach, lost gen worker, pytest failure, explicit API). This CLI is the
reading side:

    python scripts/postmortem.py                      # latest bundle in
                                                      # $ETH_SPECS_OBS_POSTMORTEM_DIR
                                                      # (./postmortems fallback)
    python scripts/postmortem.py --dir DIR            # latest bundle in DIR
    python scripts/postmortem.py BUNDLE.json          # that bundle
    python scripts/postmortem.py A.json B.json        # diff two bundles
    python scripts/postmortem.py --json [BUNDLE]      # re-emit canonical JSON
                                                      # (round-trip safe)
    python scripts/postmortem.py --list [--dir DIR]   # inventory, newest first

``make postmortem`` is the one-keystroke form of the first invocation.

Exit codes: 0 on success, 2 when no bundle is found / unreadable —
scripting-friendly (CI can probe "did anything dump?" cheaply).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

_DEFAULT_DIR = "postmortems"
_RING_TAIL = 25


def bundle_dir(explicit: str | None = None) -> str:
    return (
        explicit
        or os.environ.get("ETH_SPECS_OBS_POSTMORTEM_DIR")
        or _DEFAULT_DIR
    )


def list_bundles(dir_path: str) -> list[str]:
    """Bundle paths in ``dir_path``, newest first (mtime, then name)."""
    paths = glob.glob(os.path.join(dir_path, "postmortem-*.json"))
    return sorted(paths, key=lambda p: (os.path.getmtime(p), p), reverse=True)


def latest_bundle(dir_path: str) -> str | None:
    paths = list_bundles(dir_path)
    return paths[0] if paths else None


def load_bundle(path: str) -> dict:
    """Load + sanity-check one bundle; raises ValueError on alien JSON."""
    with open(path) as fh:
        bundle = json.load(fh)
    if not isinstance(bundle, dict) or bundle.get("bundle") != "eth-specs-postmortem":
        raise ValueError(f"{path}: not an eth-specs postmortem bundle")
    return bundle


def _fmt_time(unix: float | None) -> str:
    if not unix:
        return "?"
    return time.strftime("%Y-%m-%d %H:%M:%S UTC", time.gmtime(unix))


def _fmt_event(e: dict) -> str:
    head = f"  #{e.get('seq', '?'):>5}  {e.get('kind', '?')}"
    bits = []
    for k in ("name", "site", "kernel", "reason", "op", "case", "trigger"):
        if k in e:
            bits.append(f"{k}={e[k]}")
    if "s" in e:
        bits.append(f"{e['s'] * 1e3:.3f}ms")
    if "n" in e:
        bits.append(f"n={e['n']}")
    if e.get("trace_id"):
        bits.append(f"trace={e['trace_id'][:8]}…/{e.get('span_id', '')[:8]}")
    if "thread" in e:
        bits.append(f"[{e['thread']}]")
    return head + ("  " + " ".join(bits) if bits else "")


def _top_counters(counters: dict, n: int = 12) -> list[tuple[str, float]]:
    return sorted(counters.items(), key=lambda kv: -abs(kv[1]))[:n]


_STAGE_PREFIX = "serve.stage_ms."


def _stage_rows(reg: dict) -> list[tuple[str, dict]]:
    """(stage, histogram) rows from a bundle registry, waterfall order:
    the named stages as obs/waterfall.py emits them, then other/total."""
    hists = reg.get("histograms", {})
    rows = {
        name[len(_STAGE_PREFIX):]: h
        for name, h in hists.items()
        if name.startswith(_STAGE_PREFIX)
    }
    order = ["admit", "queue", "prep", "handoff", "dispatch_wait",
             "device", "resolve", "wire", "recovery", "other", "total"]
    ordered = [(s, rows.pop(s)) for s in order if s in rows]
    return ordered + sorted(rows.items())


def _waterfall_lines(bundle: dict) -> list[str]:
    """The waterfall view: per-stage p50/p99 table plus the HBM ledger's
    resident/high-water marks — empty if the bundle predates either."""
    reg = bundle.get("registry", {})
    lines = []
    rows = _stage_rows(reg)
    if rows:
        lines.append("  waterfall (serve.stage_ms):")
        lines.append(f"    {'stage':<14} {'count':>7} {'p50_ms':>10} {'p99_ms':>10}")
        for stage, h in rows:
            p50, p99 = h.get("p50"), h.get("p99")
            lines.append(
                f"    {stage:<14} {h.get('count', 0):>7} "
                f"{p50 if p50 is None else f'{p50:.3f}':>10} "
                f"{p99 if p99 is None else f'{p99:.3f}':>10}"
            )
    hbm = bundle.get("hbm")
    if hbm:
        lines.append(
            f"  hbm ledger: resident {hbm.get('resident_total_bytes', 0):,} B, "
            f"high water {hbm.get('high_water_bytes', 0):,} B"
        )
        for owner, nbytes in sorted((hbm.get("owners") or {}).items()):
            lines.append(f"    {owner:<24} {nbytes:>14,} B")
        for ent in hbm.get("top_entries", []) or []:
            lines.append(
                f"      {ent.get('owner')}/{ent.get('name')}: {ent.get('bytes', 0):,} B"
            )
    return lines


def summarize(bundle: dict, path: str | None = None, ring_tail: int = _RING_TAIL) -> str:
    """Human-readable one-screen account of a bundle."""
    plat = bundle.get("platform", {})
    reg = bundle.get("registry", {})
    counters = reg.get("counters", {})
    ring = bundle.get("ring", [])
    lines = [
        f"postmortem bundle{f' {path}' if path else ''}",
        f"  trigger : {bundle.get('trigger')}"
        + (f" ({bundle['detail']})" if bundle.get("detail") else ""),
        f"  time    : {_fmt_time(bundle.get('unix_time'))}   pid {bundle.get('pid')}",
        f"  platform: {plat.get('system')}/{plat.get('machine')} "
        f"python {plat.get('python')} jax {plat.get('jax_version', '—')} "
        f"backend {plat.get('jax_backend', '—')}",
        f"  argv    : {' '.join(bundle.get('argv', []))[:120]}",
    ]
    wd = reg.get("watchdog", {})
    if wd:
        lines.append(
            f"  watchdog: {wd.get('checks', 0)} checks, "
            f"{wd.get('divergences', 0)} divergences"
        )
    ckpt = bundle.get("checkpoint")
    if ckpt:
        bits = [f"verdict {ckpt.get('verdict')}"]
        if ckpt.get("manifest"):
            bits.insert(0, f"manifest {str(ckpt['manifest'])[:16]}")
        span = ckpt.get("epoch_span")
        if span:
            bits.append(f"epochs {span[0]}..{span[1]}")
        if ckpt.get("restore_ms") is not None:
            bits.append(f"restore {ckpt['restore_ms']:.0f} ms")
        lines.append("  checkpoint lineage: " + ", ".join(bits))
    if counters:
        lines.append("  top counters:")
        for name, val in _top_counters(counters):
            lines.append(f"    {name:<44} {val:g}")
    lines += _waterfall_lines(bundle)
    extra = bundle.get("extra")
    if extra:
        worker_ring = extra.get("worker_ring")
        shown = {k: v for k, v in extra.items() if k != "worker_ring"}
        if shown:
            lines.append(f"  extra   : {json.dumps(shown, sort_keys=True, default=str)[:300]}")
        if worker_ring is not None:
            lines.append(f"  dead worker's ring (last {min(len(worker_ring), ring_tail)} "
                         f"of {len(worker_ring)}):")
            lines += [_fmt_event(e) for e in worker_ring[-ring_tail:]]
    lines.append(f"  flight ring (last {min(len(ring), ring_tail)} of {len(ring)}):")
    lines += [_fmt_event(e) for e in ring[-ring_tail:]]
    return "\n".join(lines)


def diff_bundles(a: dict, b: dict, a_name: str = "A", b_name: str = "B") -> str:
    """What changed between two bundles: counter deltas, env drift, and
    each side's ring tail beyond the common prefix (same-process bundles
    share seq numbering; cross-process rings just print both tails)."""
    lines = [f"postmortem diff: {a_name} ({a.get('trigger')} @ "
             f"{_fmt_time(a.get('unix_time'))}) → {b_name} "
             f"({b.get('trigger')} @ {_fmt_time(b.get('unix_time'))})"]
    ca = a.get("registry", {}).get("counters", {})
    cb = b.get("registry", {}).get("counters", {})
    deltas = []
    for name in sorted(set(ca) | set(cb)):
        d = cb.get(name, 0) - ca.get(name, 0)
        if d:
            deltas.append((name, ca.get(name, 0), cb.get(name, 0), d))
    if deltas:
        lines.append("  counter deltas:")
        for name, va, vb, d in sorted(deltas, key=lambda r: -abs(r[3]))[:30]:
            lines.append(f"    {name:<44} {va:g} → {vb:g} ({'+' if d > 0 else ''}{d:g})")
    else:
        lines.append("  counters: identical")
    ha = a.get("registry", {}).get("histograms", {})
    hb = b.get("registry", {}).get("histograms", {})
    stage_deltas = []
    for name in sorted(set(ha) | set(hb)):
        if not name.startswith(_STAGE_PREFIX):
            continue
        pa = (ha.get(name) or {}).get("p99")
        pb = (hb.get(name) or {}).get("p99")
        if pa != pb:
            stage_deltas.append((name[len(_STAGE_PREFIX):], pa, pb))
    if stage_deltas:
        lines.append("  stage p99 deltas (serve.stage_ms):")
        for stage, pa, pb in stage_deltas:
            fa = "—" if pa is None else f"{pa:.3f}"
            fb = "—" if pb is None else f"{pb:.3f}"
            d = "" if pa is None or pb is None else f" ({pb - pa:+.3f})"
            lines.append(f"    {stage:<14} {fa} → {fb} ms{d}")
    wa = (a.get("hbm") or {}).get("high_water_bytes")
    wb = (b.get("hbm") or {}).get("high_water_bytes")
    if wa != wb:
        lines.append(f"  hbm high water: {wa} → {wb} bytes")
    ka, kb = a.get("checkpoint") or {}, b.get("checkpoint") or {}
    if ka != kb:
        lines.append("  checkpoint lineage:")
        for key in sorted(set(ka) | set(kb)):
            va, vb = ka.get(key), kb.get(key)
            if va != vb:
                lines.append(f"    {key:<12} {va} → {vb}")
    ea, eb = a.get("env", {}), b.get("env", {})
    env_drift = {
        k: (ea.get(k), eb.get(k))
        for k in sorted(set(ea) | set(eb))
        if ea.get(k) != eb.get(k)
    }
    if env_drift:
        lines.append("  env drift:")
        for k, (va, vb) in env_drift.items():
            lines.append(f"    {k}: {va!r} → {vb!r}")
    seqs_a = {e.get("seq") for e in a.get("ring", [])}
    new_in_b = [e for e in b.get("ring", []) if e.get("seq") not in seqs_a]
    same_pid = a.get("pid") == b.get("pid")
    if same_pid and new_in_b:
        lines.append(f"  ring events only in {b_name} (last {min(len(new_in_b), _RING_TAIL)}):")
        lines += [_fmt_event(e) for e in new_in_b[-_RING_TAIL:]]
    elif not same_pid:
        lines.append("  rings are from different processes; tails:")
        for name, bundle in ((a_name, a), (b_name, b)):
            lines.append(f"  {name}:")
            lines += [_fmt_event(e) for e in bundle.get("ring", [])[-5:]]
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("bundles", nargs="*",
                    help="0 paths: latest in --dir; 1: print it; 2: diff them")
    ap.add_argument("--dir", default=None,
                    help="bundle directory (default $ETH_SPECS_OBS_POSTMORTEM_DIR "
                         f"or ./{_DEFAULT_DIR})")
    ap.add_argument("--list", action="store_true", help="inventory, newest first")
    ap.add_argument("--json", action="store_true",
                    help="re-emit the bundle as canonical JSON instead of prose")
    ap.add_argument("--ring", type=int, default=_RING_TAIL,
                    help=f"ring tail length to print (default {_RING_TAIL})")
    args = ap.parse_args(argv)

    d = bundle_dir(args.dir)
    if args.list:
        paths = list_bundles(d)
        if not paths:
            print(f"no bundles under {d}", file=sys.stderr)
            return 2
        for p in paths:
            try:
                b = load_bundle(p)
                print(f"{p}  {b.get('trigger'):<24} {_fmt_time(b.get('unix_time'))} "
                      f"pid={b.get('pid')}")
            except (ValueError, OSError, json.JSONDecodeError) as exc:
                print(f"{p}  UNREADABLE ({exc})")
        return 0

    if len(args.bundles) == 2:
        try:
            a, b = (load_bundle(p) for p in args.bundles)
        except (ValueError, OSError, json.JSONDecodeError) as exc:
            print(f"error: {exc}", file=sys.stderr)
            return 2
        print(diff_bundles(a, b, *(os.path.basename(p) for p in args.bundles)))
        return 0

    path = args.bundles[0] if args.bundles else latest_bundle(d)
    if path is None:
        print(f"no bundles under {d}", file=sys.stderr)
        return 2
    try:
        bundle = load_bundle(path)
    except (ValueError, OSError, json.JSONDecodeError) as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 2
    if args.json:
        # canonical re-emission: json.loads(output) == the bundle on disk
        print(json.dumps(bundle, indent=1, sort_keys=True))
    else:
        print(summarize(bundle, path=path, ring_tail=args.ring))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
