"""dstpu-benchdiff CLI: diff two bench records under the committed policy.

Exit codes: 0 — no regression (improvements / within-band / missing are all
fine); 1 — at least one policy metric regressed past its tolerance band;
2 — usage/load error (unreadable record, malformed policy).
"""

import argparse
import json
import os
import sys
from typing import List, Optional

from .diffcore import (VERDICT_IMPROVEMENT, VERDICT_MISSING, VERDICT_REGRESSION,
                       diff_metrics, load_bench, load_policy)

_VERDICT_GLYPH = {VERDICT_REGRESSION: "✗", VERDICT_IMPROVEMENT: "✓",
                  VERDICT_MISSING: "·"}


def _find_policy(explicit: Optional[str], base_path: str) -> str:
    """Policy resolution: --policy wins; else benchtrack.json next to the
    base record, else in the cwd."""
    if explicit:
        return explicit
    for candidate in (os.path.join(os.path.dirname(os.path.abspath(base_path)),
                                   "benchtrack.json"),
                      "benchtrack.json"):
        if os.path.exists(candidate):
            return candidate
    raise FileNotFoundError(
        "no benchtrack.json found next to the base record or in the cwd "
        "(pass --policy explicitly)")


def _fmt(value) -> str:
    if value is None:
        return "-"
    return f"{value:.4g}"


def render_text(rows: List[dict], base: dict, cand: dict) -> str:
    lines = [f"benchdiff: {base['path']} (rc={base['rc']}) -> "
             f"{cand['path']} (rc={cand['rc']})"]
    w = max((len(r["metric"]) for r in rows), default=6)
    for r in rows:
        glyph = _VERDICT_GLYPH.get(r["verdict"], " ")
        pct = r.get("pct_change")
        pct_s = f"{pct:+7.2f}%" if pct is not None else "       -"
        note = f"  ({r['note']})" if r.get("note") else ""
        lines.append(f"  {glyph} {r['metric']:<{w}}  {_fmt(r['base']):>10} -> "
                     f"{_fmt(r['candidate']):>10}  {pct_s}  "
                     f"[{r['direction']} ±{r['tolerance_pct']:g}%]  "
                     f"{r['verdict']}{note}")
    counts = {}
    for r in rows:
        counts[r["verdict"]] = counts.get(r["verdict"], 0) + 1
    summary = ", ".join(f"{n} {v}" for v, n in sorted(counts.items()))
    lines.append(f"  -- {summary or 'no metrics judged'}")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="dstpu-benchdiff",
        description="Diff two bench records (a driver's command-wrapper "
                    "record or the plain JSON of a fresh run) under the benchtrack.json "
                    "direction+tolerance policy; exit 1 on regression.")
    parser.add_argument("base", help="baseline record (JSON file)")
    parser.add_argument("candidate", help="candidate record (JSON file)")
    parser.add_argument("--policy", default=None,
                        help="policy file (default: benchtrack.json next to "
                             "the base record, then ./benchtrack.json)")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="emit the verdict rows as JSON instead of text")
    args = parser.parse_args(argv)
    try:
        base = load_bench(args.base)
        cand = load_bench(args.candidate)
        policy = load_policy(_find_policy(args.policy, args.base))
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"dstpu-benchdiff: {e}", file=sys.stderr)
        return 2
    rows = diff_metrics(base["metrics"], cand["metrics"], policy)
    regressed = [r for r in rows if r["verdict"] == VERDICT_REGRESSION]
    if args.as_json:
        print(json.dumps({"base": base["path"], "candidate": cand["path"],
                          "rows": rows, "regressions": len(regressed),
                          "ok": not regressed}, indent=2))
    else:
        print(render_text(rows, base, cand))
    return 1 if regressed else 0


if __name__ == "__main__":
    sys.exit(main())
