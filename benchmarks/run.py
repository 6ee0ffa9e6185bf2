"""Benchmark harness: one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus commented detail lines).
Run:  PYTHONPATH=src python -m benchmarks.run [--only SUBSTR] [--json]

``--json`` emits ONE machine-readable JSON document on stdout instead of
CSV; everything else (bench detail prints, tracebacks) goes to stderr, so
``python -m benchmarks.run --json | jq .`` just works.  Tracebacks go to
stderr in both modes -- stdout stays parseable.

Registered benches (see benchmarks.paper_benches.ALL): fig2..fig5, the
smart-update tables, the fused-SINR kernel check, and ``mac_episode``
(scan-compiled TTI engine vs per-TTI graph dispatch).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import traceback


def main(argv=None) -> None:
    from benchmarks import paper_benches
    from repro.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="run only benchmarks whose name contains SUBSTR")
    ap.add_argument("--smoke", action="store_true",
                    help="shrunken shapes for CI: fast, but regression "
                         "gates (per-RB episode cost) still assert")
    ap.add_argument("--json", action="store_true",
                    help="one JSON document on stdout (detail -> stderr)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    paper_benches.SMOKE = args.smoke
    benches = [b for b in paper_benches.ALL if args.only in b.__name__]
    if not benches:
        ap.error(f"no benchmark name contains {args.only!r}; have: "
                 + ", ".join(b.__name__ for b in paper_benches.ALL))

    # --json: stdout must be pure JSON, so the benches' own detail prints
    # ("# fig2: ..." lines) are rerouted to stderr for the whole run
    detail = contextlib.redirect_stdout(sys.stderr) if args.json \
        else contextlib.nullcontext()
    results = []
    failures = 0
    if not args.json:
        print("name,us_per_call,derived")
    with detail:
        for bench in benches:
            try:
                name, us, derived = bench()
                results.append({"bench": bench.__name__, "name": name,
                                "us_per_call": round(float(us), 2),
                                "derived": derived, "ok": True})
                if not args.json:
                    print(f"{name},{us:.1f},{derived}")
                    sys.stdout.flush()
            except Exception as e:
                failures += 1
                results.append({"bench": bench.__name__,
                                "name": bench.__name__,
                                "us_per_call": None, "derived": None,
                                "ok": False,
                                "error": f"{type(e).__name__}: {e}"})
                if not args.json:
                    print(f"{bench.__name__},FAILED,-")
                traceback.print_exc(file=sys.stderr)
    if args.json:
        json.dump({"smoke": bool(args.smoke), "failures": failures,
                   "results": results}, sys.stdout, indent=2,
                  sort_keys=True, default=str)
        sys.stdout.write("\n")
    if failures:
        raise SystemExit(f"{failures} benchmarks failed")


if __name__ == "__main__":
    main()
