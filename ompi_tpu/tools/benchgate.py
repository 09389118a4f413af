"""benchgate CLI — the enforced perf ratchet over the bench trajectory.

Round files (``BENCH_r*.json`` / ``MULTICHIP_r*.json``, one per recorded
bench run) hold every round's rows. This gate makes them a contract:
given a *current* set of rows (a fresh ``bench.py`` run, the live
partial file, or a round file), every (row, metric) with history must
not regress past the best trajectory value by more than the allowance.

Usage::

    python -m ompi_tpu.tools.benchgate [--root DIR] [--current FILE]
        [--allowance PCT] [--dry-run] [--self] [--json]
    python bench.py --gate [--dry-run | --current FILE ...]

Semantics:

- **Baselines** are the best-ever value per (row, metric) across the
  trajectory, direction-aware: throughput-shaped metrics (``gbps``,
  ``busbw``, ``hit_rate``, ``speedup``...) ratchet upward, latency-
  shaped ones (``*_us``, ``*_ms``, ``p50``/``p99``/``rtt``,
  ``overhead_pct``...) downward. Metrics that match neither shape are
  ignored — the gate never guesses a direction.
- **Degraded rows are excused, not silent**: a row tagged
  ``degraded=true`` (bench ran inside a quarantine window) or coming
  from a round whose ``rc != 0`` (no working device; host rows only)
  is reported but never fails the gate.
- ``--dry-run`` only validates/loads the trajectory (the tier-1 seam:
  malformed round files fail fast with exit 2, before a 25-minute
  bench run would trip over them).
- ``--self`` replays the trajectory: each round gated against the
  rounds before it (the newest-round regression check).

Exit codes: 0 pass, 1 ratchet break, 2 malformed trajectory / run
failure — the lint CLI's contract.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Optional

#: Metric-name fragments that mark a higher-is-better series.
_HIGHER = ("gbps", "busbw", "gb_s", "hit_rate", "speedup", "ratio_x",
           "overlap_pct", "ticks_sampled", "_per_s", "ag_elided")
#: Fragments that mark a lower-is-better series. ``overhead_pct``
#: rides the _pct absolute-slack path in _is_regression.
_LOWER = ("p50", "p99", "_us", "_ms", "rtt", "latency", "detect_ms",
          "overhead_pct", "tune_ms", "restore_ms", "degradation_pct",
          "convergence_ticks", "rejoin_steps", "blip")

DEFAULT_ALLOWANCE = 0.25


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def direction(metric: str) -> Optional[str]:
    """'higher' / 'lower' / None (ignored) for a metric name. Checked
    lower-first so ``overhead_pct`` never reads as throughput."""
    m = metric.lower()
    if any(t in m for t in _LOWER):
        return "lower"
    if any(t in m for t in _HIGHER):
        return "higher"
    return None


class GateError(Exception):
    """Malformed trajectory / unusable input (exit 2)."""


def _load_doc(path: str) -> dict:
    try:
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError) as exc:
        raise GateError(f"{path}: unreadable ({exc})") from exc
    if not isinstance(doc, dict):
        raise GateError(f"{path}: expected a JSON object, got "
                        f"{type(doc).__name__}")
    return doc


def _round_rows(doc: dict, path: str) -> dict[str, dict]:
    """{row_name: {metric: value, ..., "degraded": bool}} for one
    trajectory round. Tolerates the MULTICHIP shape (rc=0 but no
    parsed detail) by contributing nothing."""
    parsed = doc.get("parsed")
    if parsed is None:
        return {}
    if not isinstance(parsed, dict):
        raise GateError(f"{path}: 'parsed' is not an object")
    detail = parsed.get("detail")
    if detail is None:
        return {}
    if not isinstance(detail, dict):
        raise GateError(f"{path}: 'parsed.detail' is not an object")
    round_failed = doc.get("rc", 0) != 0
    rows: dict[str, dict] = {}

    def _take(name: str, row) -> None:
        if not isinstance(row, dict) or "error" in row:
            return
        metrics = {k: float(v) for k, v in row.items()
                   if isinstance(v, (int, float))
                   and not isinstance(v, bool)}
        if not metrics:
            return
        metrics["degraded"] = bool(row.get("degraded")) or round_failed
        rows[name] = metrics

    for name, row in detail.items():
        if name in ("error", "phase", "partial"):
            continue
        _take(name, row)
    partial = detail.get("partial")
    if isinstance(partial, dict):
        for name, row in partial.items():
            _take(name, row)
    return rows


def load_trajectory(root: str) -> list[tuple[str, dict[str, dict]]]:
    """[(path, rows)] for every trajectory file under ``root``, in
    round order. Raises GateError on a malformed file."""
    paths = sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))) + \
        sorted(glob.glob(os.path.join(root, "MULTICHIP_r*.json")))
    if not paths:
        raise GateError(f"no BENCH_r*/MULTICHIP_r* files under {root}")
    return [(p, _round_rows(_load_doc(p), p)) for p in paths]


def baselines(rounds: list[tuple[str, dict]]) -> dict:
    """{(row, metric): best value} over the trajectory (direction-
    aware; metrics with no direction never enter)."""
    best: dict[tuple[str, str], float] = {}
    for _path, rows in rounds:
        for rname, metrics in rows.items():
            for metric, value in metrics.items():
                if metric == "degraded":
                    continue
                d = direction(metric)
                if d is None:
                    continue
                k = (rname, metric)
                if k not in best:
                    best[k] = value
                elif d == "higher":
                    best[k] = max(best[k], value)
                else:
                    best[k] = min(best[k], value)
    return best


def _is_regression(metric: str, cur: float, base: float,
                   allowance: float) -> bool:
    d = direction(metric)
    if d is None:
        return False
    if metric.lower().endswith("_pct"):
        # percentage-point rows hover near zero where relative slack
        # degenerates; use absolute points
        slack = max(2.0, abs(base) * allowance)
    else:
        slack = abs(base) * allowance
    if d == "lower":
        return cur > base + slack
    return cur < base - slack


def gate_rows(current: dict[str, dict], best: dict,
              allowance: float) -> tuple[list[dict], list[dict]]:
    """(breaks, excused) comparing current rows to the baselines."""
    breaks: list[dict] = []
    excused: list[dict] = []
    for rname in sorted(current):
        metrics = current[rname]
        if not isinstance(metrics, dict):
            continue
        degraded = bool(metrics.get("degraded"))
        for metric in sorted(metrics):
            value = metrics[metric]
            if metric == "degraded" or isinstance(value, bool) \
                    or not isinstance(value, (int, float)):
                continue
            base = best.get((rname, metric))
            if base is None:
                continue
            if _is_regression(metric, float(value), base, allowance):
                item = {"row": rname, "metric": metric,
                        "current": float(value), "best": base,
                        "direction": direction(metric)}
                (excused if degraded else breaks).append(item)
    return breaks, excused


def _current_rows(path: str) -> dict[str, dict]:
    """Rows from a 'current' file, accepting any of the shapes the
    repo produces: a round file (``parsed.detail``), the live partial
    dump (``{"phase", "rows"}``), or a bare ``{row: {metric: v}}``."""
    doc = _load_doc(path)
    if "parsed" in doc:
        return _round_rows(doc, path)
    rows = doc.get("rows") if isinstance(doc.get("rows"), dict) else doc
    out: dict[str, dict] = {}
    for name, row in rows.items():
        if isinstance(row, dict) and "error" not in row:
            metrics = {k: float(v) for k, v in row.items()
                       if isinstance(v, (int, float))
                       and not isinstance(v, bool)}
            if metrics:
                metrics["degraded"] = bool(row.get("degraded"))
                out[name] = metrics
    return out


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="benchgate",
        description="gate bench rows against the BENCH_r*/MULTICHIP_r* "
                    "trajectory")
    ap.add_argument("--root", default=repo_root(),
                    help="directory holding the trajectory files")
    ap.add_argument("--current",
                    help="rows to gate (round file, live partial dump, "
                         "or bare row dict); default: "
                         "docs/BENCH_PARTIAL_LIVE.json when present")
    ap.add_argument("--allowance", type=float,
                    default=DEFAULT_ALLOWANCE * 100,
                    help="regression allowance in percent "
                         "(default %(default)s)")
    ap.add_argument("--dry-run", action="store_true",
                    help="validate/load the trajectory only")
    ap.add_argument("--self", dest="self_check", action="store_true",
                    help="replay: gate each round against the rounds "
                         "before it")
    ap.add_argument("--json", dest="as_json", action="store_true",
                    help="machine-readable report on stdout")
    args = ap.parse_args(argv)
    allowance = max(0.0, args.allowance) / 100.0

    try:
        rounds = load_trajectory(args.root)
    except GateError as exc:
        print(f"benchgate: {exc}", file=sys.stderr)
        return 2

    report: dict = {
        "rounds": [os.path.basename(p) for p, _ in rounds],
        "tracked_series": len(baselines(rounds)),
        "allowance_pct": allowance * 100,
        "breaks": [],
        "excused": [],
    }

    if args.dry_run:
        report["mode"] = "dry-run"
        print(json.dumps(report, indent=1) if args.as_json else
              f"benchgate: trajectory ok — {len(rounds)} round file(s),"
              f" {report['tracked_series']} tracked series")
        return 0

    if args.self_check:
        report["mode"] = "self"
        for i in range(1, len(rounds)):
            best = baselines(rounds[:i])
            breaks, excused = gate_rows(rounds[i][1], best, allowance)
            tag = os.path.basename(rounds[i][0])
            for b in breaks:
                b["round"] = tag
            for e in excused:
                e["round"] = tag
            report["breaks"].extend(breaks)
            report["excused"].extend(excused)
    else:
        report["mode"] = "gate"
        current_path = args.current or os.path.join(
            args.root, "docs", "BENCH_PARTIAL_LIVE.json")
        if not os.path.exists(current_path):
            print(f"benchgate: no current rows at {current_path} "
                  "(run bench.py, or pass --current)", file=sys.stderr)
            return 2
        try:
            current = _current_rows(current_path)
        except GateError as exc:
            print(f"benchgate: {exc}", file=sys.stderr)
            return 2
        best = baselines(rounds)
        report["breaks"], report["excused"] = gate_rows(
            current, best, allowance)
        report["current"] = os.path.basename(current_path)
        report["rows_checked"] = len(current)

    if args.as_json:
        print(json.dumps(report, indent=1))
    else:
        for e in report["excused"]:
            print(f"benchgate: excused (degraded) {e['row']}."
                  f"{e['metric']}: {e['current']:g} vs best "
                  f"{e['best']:g}")
        for b in report["breaks"]:
            print(f"benchgate: RATCHET BREAK {b['row']}.{b['metric']}: "
                  f"{b['current']:g} vs best {b['best']:g} "
                  f"({b['direction']} is better)")
        if not report["breaks"]:
            print(f"benchgate: pass ({report['tracked_series']} "
                  f"series, {len(report['excused'])} excused)")
    return 1 if report["breaks"] else 0


if __name__ == "__main__":
    sys.exit(main())
