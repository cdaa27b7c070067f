#!/usr/bin/env python
"""Backend-aware bench regression gate (docs/observability.md §gate).

Compares bench artifacts pairwise, oldest→newest, refusing cross-backend
deltas (ROADMAP "bench trajectory caveat": r3/r5 were CPU-fallback rounds,
r2 ran the accelerator — those ratios are not a trend, they are a hardware
swap). Examples::

    # same-backend pair: deltas reported, noise-thresholded
    python scripts/bench_compare.py BENCH_r03.json BENCH_r05.json

    # cross-backend pair: metrics marked `incomparable`, never scored
    python scripts/bench_compare.py BENCH_DETAILS.json BENCH_r05.json

    # the ci.sh advisory stage: the two newest checked-in artifacts
    python scripts/bench_compare.py --newest 2 --json verdict.json

Exit codes: 0 — verdicts printed (advisory mode, the default: a measured
regression is a finding, not a CI failure); 1 — ``--strict`` and at least
one comparable metric regressed; 2 — schema error (unreadable artifact,
malformed thresholds file). ci.sh runs the advisory mode so schema rot
fails the build while slow-box noise does not.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from photon_tpu.obs.analysis.artifacts import (  # noqa: E402
    ArtifactError,
    newest_artifacts,
)
from photon_tpu.obs.analysis.bench_compare import (  # noqa: E402
    compare_artifacts,
    format_verdict,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_compare",
        description="Backend-aware bench artifact comparison.")
    ap.add_argument("artifacts", nargs="*",
                    help="two or more BENCH_r*.json / BENCH_DETAILS*.json, "
                         "oldest first")
    ap.add_argument("--newest", type=int, default=None, metavar="K",
                    help="ignore positional args; compare the K newest "
                         "parseable checked-in artifacts")
    ap.add_argument("--json", dest="json_out", default=None,
                    help="write the machine-readable verdict here "
                         "('-' for stdout)")
    ap.add_argument("--thresholds", default=None,
                    help="JSON file of {metric: relative_threshold} "
                         "overrides (e.g. {\"serve_p99_ms\": 0.5})")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 when any comparable metric regressed "
                         "(default: advisory, exit 0)")
    args = ap.parse_args(argv)

    if args.newest is not None:
        paths = newest_artifacts(REPO, k=args.newest)
        if len(paths) < 2:
            print("bench_compare: fewer than 2 parseable bench artifacts "
                  "checked in; nothing to compare (advisory ok)")
            return 0
    else:
        paths = args.artifacts
        if len(paths) < 2:
            ap.error("need at least two artifacts (or --newest K)")

    thresholds = None
    if args.thresholds:
        try:
            with open(args.thresholds) as f:
                thresholds = {
                    str(k): float(v) for k, v in json.load(f).items()
                }
        except (OSError, ValueError, TypeError, AttributeError) as e:
            print(f"bench_compare: schema error in --thresholds: {e}",
                  file=sys.stderr)
            return 2

    try:
        doc = compare_artifacts(paths, thresholds=thresholds)
    except ArtifactError as e:
        print(f"bench_compare: schema error: {e}", file=sys.stderr)
        return 2

    print(format_verdict(doc))
    if args.json_out == "-":
        print(json.dumps(doc, indent=2))
    elif args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"verdict written to {args.json_out}")

    if args.strict and doc["overall"] == "regressed":
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
