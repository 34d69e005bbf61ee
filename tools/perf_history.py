#!/usr/bin/env python
"""Merge the banked perf artifacts into one trajectory report.

The repo banked performance evidence in disconnected shapes: the
driver's device-bench rounds (``BENCH_r*.json`` — one JSON record per
round, ``parsed: null`` or ``goodput: null`` when device init never
returned and the bench watchdog exited with rc=75), the multichip
dryrun rounds (``MULTICHIP_r*.json``), the decode-bench rounds
(``DECODE_r*.json``), plus the measured sweep tables in ``BASELINE.md`` (where the best banked config —
flash BQ=512 BK=512 at 0.3789 MFU — actually lives). Until this tool
nothing read them together, so "is the MFU trajectory still pointed at
the 0.40 north star, and did any round regress" required a human diff.

This tool folds them into one report:

- every device round renders — **blind rounds included**, with their
  reason (a wall of rc=75 wedges must read as "no device data since
  r2", never as "no regressions");
- the best banked MFU config is reproduced from the artifacts
  (BENCH rounds ∪ BASELINE.md sweep rows) and compared to the 0.40
  north star;
- measured rounds are swept for ±5% regressions against the best
  preceding round (``--tolerance``); ``--check`` turns any flag into
  exit 1 — the CI ``goodput-smoke`` job's trajectory gate.

    python tools/perf_history.py                  # text report, repo root
    python tools/perf_history.py --dir /path      # another artifact root
    python tools/perf_history.py --json           # machine-readable
    python tools/perf_history.py --check          # exit 1 on regression

Exit: 0 rendered (no regression under --check), 1 regression flagged
under --check, 2 unreadable root / no artifacts at all.

Pure stdlib on purpose (the ``tools/postmortem.py`` convention): the
trajectory must render on a box where the package cannot even import.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional

#: the ROADMAP north star every trajectory is measured against
NORTH_STAR_MFU = 0.40


def _load_json(path: str) -> Optional[Dict]:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _round_no(path: str) -> int:
    m = re.search(r"_r(\d+)\.json$", os.path.basename(path))
    return int(m.group(1)) if m else -1


def collect_bench(root: str) -> List[Dict[str, Any]]:
    """``BENCH_r*.json`` → one row per round, ascending. A round is
    BLIND when it produced no measured value (``parsed: null`` from a
    pre-PR-15 wedge, or the structured ``goodput: null`` abort record);
    its reason rides along so the trajectory explains itself."""
    rows = []
    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json")),
                       key=_round_no):
        doc = _load_json(path)
        if not isinstance(doc, dict):
            continue
        parsed = doc.get("parsed")
        rc = doc.get("rc")
        row: Dict[str, Any] = {"round": doc.get("n", _round_no(path)),
                               "rc": rc, "file": os.path.basename(path)}
        if not isinstance(parsed, dict) or parsed.get("value") is None:
            row["blind"] = True
            row["reason"] = (parsed.get("error")
                            if isinstance(parsed, dict) else None) \
                or f"no parsed output (rc={rc})"
            # retry history (bench watchdog, post-elastic): attempts > 1
            # means the round was given a bounded retry window and STILL
            # wedged — a different operational story than a single-shot
            # timeout (pre-retry records carry no attempts field: None)
            row["attempts"] = (parsed.get("attempts")
                               if isinstance(parsed, dict) else None)
        else:
            extra = parsed.get("extra") or {}
            row.update(blind=False, metric=parsed.get("metric"),
                       value=parsed.get("value"), unit=parsed.get("unit"),
                       mfu=extra.get("mfu"),
                       step_ms=extra.get("step_ms"),
                       backend=extra.get("backend"))
        rows.append(row)
    return rows


def collect_multichip(root: str) -> List[Dict[str, Any]]:
    rows = []
    for path in sorted(glob.glob(os.path.join(root, "MULTICHIP_r*.json")),
                       key=_round_no):
        doc = _load_json(path)
        if not isinstance(doc, dict):
            continue
        rows.append({"round": _round_no(path),
                     "n_devices": doc.get("n_devices"),
                     "ok": doc.get("ok"), "rc": doc.get("rc"),
                     "skipped": doc.get("skipped"),
                     "file": os.path.basename(path)})
    return rows


def collect_decode(root: str) -> List[Dict[str, Any]]:
    """``DECODE_r*.json`` → one row per decode-serving round, ascending.
    Each artifact is a ``serve_bench --decode`` record (or the driver's
    ``{"parsed": record, "rc": N}`` wrapper): tokens/sec, ITL p50/p99,
    the statically priced capacity vs the pool's admission limit, and
    the post-warmup compile count — the decode twin of the BENCH rows."""
    rows = []
    for path in sorted(glob.glob(os.path.join(root, "DECODE_r*.json")),
                       key=_round_no):
        doc = _load_json(path)
        if not isinstance(doc, dict):
            continue
        rec = doc.get("parsed") if isinstance(doc.get("parsed"), dict) \
            else doc
        row: Dict[str, Any] = {"round": _round_no(path),
                               "file": os.path.basename(path)}
        if rec.get("value") is None:
            row["blind"] = True
            row["reason"] = rec.get("error") or \
                f"no parsed output (rc={doc.get('rc')})"
        else:
            extra = rec.get("extra") or {}
            cap = extra.get("capacity") or {}
            row.update(
                blind=False, tokens_per_sec=rec.get("value"),
                itl_ms_p50=extra.get("itl_ms_p50"),
                itl_ms_p99=extra.get("itl_ms_p99"),
                capacity=cap.get("max_sequences"),
                admission_limit=extra.get("admission_limit"),
                post_warmup_compiles=extra.get("post_warmup_compiles"),
                backend=extra.get("backend"))
        rows.append(row)
    return rows


def decode_regressions(rows: List[Dict],
                       tolerance: float = 0.05) -> List[str]:
    """The decode sweep: tokens/sec per round against the best preceding
    measured round (same blind-round semantics as :func:`regressions`),
    plus hard flags — a post-warmup compile or a capacity/admission
    mismatch is a broken contract at any throughput."""
    flags: List[str] = []
    best: Optional[float] = None
    best_round = None
    for row in rows:
        if row.get("blind"):
            continue
        if row.get("post_warmup_compiles"):
            flags.append(f"DECODE r{row['round']}: "
                         f"{row['post_warmup_compiles']} post-warmup "
                         "compile(s) — the warm contract is broken")
        if row.get("capacity") is not None \
                and row.get("admission_limit") is not None \
                and row["capacity"] != row["admission_limit"]:
            flags.append(f"DECODE r{row['round']}: priced capacity "
                         f"{row['capacity']} != pool admission limit "
                         f"{row['admission_limit']}")
        tps = row.get("tokens_per_sec")
        if not tps:
            continue
        if best is not None and tps < (1.0 - tolerance) * best:
            flags.append(
                f"DECODE r{row['round']}: {tps:.4g} tokens/sec is "
                f"{100.0 * (tps / best - 1):.1f}% vs best {best:.4g} "
                f"(r{best_round}) — beyond the ±{tolerance * 100:.0f}% "
                "tolerance")
        if best is None or tps > best:
            best, best_round = tps, row["round"]
    return flags


#: a BASELINE.md sweep row: |config|step ms|MFU| — cells may carry
#: ``**bold**`` / trailing ``*`` contention marks
_MD_ROW = re.compile(r"^\s*\|([^|]+)\|([^|]+)\|([^|]+)\|\s*$")


def _md_float(cell: str) -> Optional[float]:
    cell = cell.replace("*", "").replace(",", "").strip()
    try:
        return float(cell)
    except ValueError:
        return None


def collect_baseline_sweeps(root: str) -> List[Dict[str, Any]]:
    """Measured sweep rows from BASELINE.md's markdown tables (any
    3-cell row whose last cell is an MFU-shaped float in (0, 1) and
    whose middle cell is a step time) — this is where the banked
    0.3789-MFU best config (flash BQ=512 BK=512) actually lives."""
    path = os.path.join(root, "BASELINE.md")
    rows: List[Dict[str, Any]] = []
    try:
        with open(path, encoding="utf-8") as f:
            lines = f.readlines()
    except OSError:
        return rows
    for line in lines:
        m = _MD_ROW.match(line)
        if not m:
            continue
        config = m.group(1).replace("*", "").strip()
        step_ms = _md_float(m.group(2))
        mfu = _md_float(m.group(3))
        if step_ms is None or mfu is None or not (0.0 < mfu < 1.0):
            continue   # headers, separators, "pathological" rows
        rows.append({"config": config, "step_ms": step_ms, "mfu": mfu,
                     "source": "BASELINE.md"})
    return rows


def best_banked(bench: List[Dict], sweeps: List[Dict]) -> Optional[Dict]:
    """The best MFU any banked artifact records, with its config."""
    cands = [{"mfu": r["mfu"], "config": r.get("metric"),
              "source": r["file"]}
             for r in bench if not r.get("blind") and r.get("mfu")]
    cands += [{"mfu": r["mfu"], "config": r["config"], "source": r["source"]}
              for r in sweeps]
    if not cands:
        return None
    best = max(cands, key=lambda c: c["mfu"])
    best["vs_north_star"] = round(best["mfu"] / NORTH_STAR_MFU, 4)
    return best


def regressions(bench: List[Dict], tolerance: float = 0.05) -> List[str]:
    """±tolerance sweep over the measured rounds, each against the best
    preceding measured MFU. Blind rounds carry no number so they can
    never flag — but they also never reset the best, so a regression
    after a blind gap is still caught."""
    flags: List[str] = []
    best: Optional[float] = None
    best_round = None
    for row in bench:
        if row.get("blind") or not row.get("mfu"):
            continue
        mfu = row["mfu"]
        if best is not None and mfu < (1.0 - tolerance) * best:
            flags.append(
                f"BENCH r{row['round']}: mfu {mfu:.4g} is "
                f"{100.0 * (mfu / best - 1):.1f}% vs best {best:.4g} "
                f"(r{best_round}) — beyond the ±{tolerance * 100:.0f}% "
                "tolerance")
        if best is None or mfu > best:
            best, best_round = mfu, row["round"]
    return flags


def collect(root: str, tolerance: float = 0.05) -> Dict[str, Any]:
    """The whole merged trajectory as one JSON-ready dict."""
    bench = collect_bench(root)
    sweeps = collect_baseline_sweeps(root)
    decode = collect_decode(root)
    doc = {
        "root": os.path.abspath(root),
        "tolerance": tolerance,
        "north_star_mfu": NORTH_STAR_MFU,
        "bench_rounds": bench,
        "blind_rounds": sum(1 for r in bench if r.get("blind")),
        "multichip_rounds": collect_multichip(root),
        "baseline_sweeps": sweeps,
        "decode_rounds": decode,
        "best_banked": best_banked(bench, sweeps),
        "regressions": (regressions(bench, tolerance)
                        + decode_regressions(decode, tolerance)),
    }
    return doc


def summary(root: str, tolerance: float = 0.05) -> Dict[str, Any]:
    """The compact form: best banked config, round counts, regression
    flags."""
    doc = collect(root, tolerance)
    return {"best_banked": doc["best_banked"],
            "rounds": len(doc["bench_rounds"]),
            "blind_rounds": doc["blind_rounds"],
            "regressions": doc["regressions"]}


def render(doc: Dict[str, Any]) -> str:
    """The trajectory as one readable text report."""
    out: List[str] = [f"perf history — {doc['root']}"]

    def section(title: str) -> None:
        out.extend(["", f"== {title} " + "=" * max(0, 60 - len(title))])

    section("device bench rounds")
    if not doc["bench_rounds"]:
        out.append("  (no BENCH_r*.json artifacts)")
    for r in doc["bench_rounds"]:
        if r.get("blind"):
            att = r.get("attempts")
            retry = (f"  after {att} attempts" if isinstance(att, int)
                     and att > 1 else
                     ("  (no retry window)" if att == 1 else ""))
            out.append(f"  r{r['round']:02d}  BLIND  rc={r['rc']}  "
                       f"— {r['reason']}{retry}")
        else:
            mfu = f"{r['mfu']:.4f}" if r.get("mfu") is not None else "?"
            out.append(f"  r{r['round']:02d}  mfu {mfu}  "
                       f"{r.get('value')} {r.get('unit')}  "
                       f"({r.get('metric')}, {r.get('backend')})")

    section("banked sweep configs (BASELINE.md)")
    best = doc.get("best_banked") or {}
    for r in doc["baseline_sweeps"]:
        star = "  <- best banked" if best and r["mfu"] == best.get("mfu") \
            and r["config"] == best.get("config") else ""
        out.append(f"  {r['config']:<36} {r['step_ms']:>7.1f} ms  "
                   f"MFU {r['mfu']:.4f}{star}")
    if not doc["baseline_sweeps"]:
        out.append("  (no parseable sweep rows)")

    section("decode serving rounds")
    for r in doc.get("decode_rounds") or []:
        if r.get("blind"):
            out.append(f"  r{r['round']:02d}  BLIND  — {r['reason']}")
        else:
            itl50 = r.get("itl_ms_p50")
            itl99 = r.get("itl_ms_p99")
            itl = (f"ITL p50 {itl50}/p99 {itl99} ms"
                   if itl50 is not None else "ITL ?")
            out.append(
                f"  r{r['round']:02d}  {r.get('tokens_per_sec')} "
                f"tokens/sec  {itl}  capacity {r.get('capacity')} "
                f"(admits {r.get('admission_limit')})  "
                f"recompiles {r.get('post_warmup_compiles')}  "
                f"({r.get('backend')})")
    if not doc.get("decode_rounds"):
        out.append("  (no DECODE_r*.json artifacts)")

    section("multichip rounds")
    for r in doc["multichip_rounds"]:
        verdict = "ok" if r.get("ok") else (
            "skipped" if r.get("skipped") else f"FAIL rc={r.get('rc')}")
        out.append(f"  r{r['round']:02d}  {r.get('n_devices')} devices  "
                   f"{verdict}")
    if not doc["multichip_rounds"]:
        out.append("  (no MULTICHIP_r*.json artifacts)")

    section("verdict")
    if best:
        out.append(f"  best banked MFU {best['mfu']:.4f} "
                   f"({best['config']}, {best['source']}) — "
                   f"{best['vs_north_star']:.4f}x the "
                   f"{doc['north_star_mfu']:.2f} north star")
    else:
        out.append("  no measured MFU banked anywhere")
    blind = doc["blind_rounds"]
    if blind:
        newest = doc["bench_rounds"][-1] if doc["bench_rounds"] else None
        tail = (" — the newest round is blind: the device bench has no "
                "current claim" if newest and newest.get("blind") else "")
        out.append(f"  {blind} blind round(s) (device init timeout / no "
                   f"parsed output){tail}")
    if doc["regressions"]:
        for flag in doc["regressions"]:
            out.append(f"  !! REGRESSION {flag}")
    else:
        out.append("  regressions: none flagged across measured rounds")
    return "\n".join(out) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dir", default=".",
                    help="artifact root (default: current directory)")
    ap.add_argument("--json", action="store_true",
                    help="emit the merged trajectory as compact JSON")
    ap.add_argument("--tolerance", type=float, default=0.05,
                    help="relative regression tolerance (default 0.05)")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 when any measured round regressed "
                         "beyond the tolerance (the CI trajectory gate)")
    args = ap.parse_args(argv)

    if not os.path.isdir(args.dir):
        print(f"perf_history: not a directory: {args.dir}", file=sys.stderr)
        return 2
    doc = collect(args.dir, args.tolerance)
    if not doc["bench_rounds"] and not doc["multichip_rounds"] \
            and not doc["baseline_sweeps"] and not doc["decode_rounds"]:
        print(f"perf_history: no BENCH_r*/MULTICHIP_r*/DECODE_r*/"
              f"BASELINE.md artifacts under {args.dir}",
              file=sys.stderr)
        return 2
    if args.json:
        json.dump(doc, sys.stdout, separators=(",", ":"))
        sys.stdout.write("\n")
    else:
        sys.stdout.write(render(doc))
    if args.check and doc["regressions"]:
        for flag in doc["regressions"]:
            print(f"perf_history: {flag}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
