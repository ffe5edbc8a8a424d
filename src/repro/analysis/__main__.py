"""CLI for masklint: ``python -m repro.analysis [paths ...]``.

Exit codes: 0 clean, 1 findings, 2 usage error.
"""

from __future__ import annotations

import argparse
import sys

from .core import (SUPPRESSION_FILE, all_rules, report_json, report_text,
                   run_paths)

_DEFAULT_PATHS = ("src", "examples")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="masklint: static analysis of the repo's correctness "
                    "contracts (lock/epoch/bounds/kernel/stats rules)")
    ap.add_argument("paths", nargs="*",
                    help=f"files or directories to scan (default: the "
                         f"{'/'.join(_DEFAULT_PATHS)} trees that exist "
                         f"under the current directory)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--rules", metavar="R1,R2",
                    help="comma-separated subset of rules to run")
    ap.add_argument("--suppressions", metavar="FILE",
                    help=f"suppression file (default: ./{SUPPRESSION_FILE})")
    ap.add_argument("--list", action="store_true",
                    help="list the registered rules and exit")
    ap.add_argument("--explain", metavar="RULE",
                    help="print a rule's invariant documentation and exit")
    args = ap.parse_args(argv)

    registry = all_rules()
    if args.list:
        width = max(len(n) for n in registry)
        for name in sorted(registry):
            print(f"{name:<{width}}  {registry[name].summary}")
        return 0
    if args.explain:
        cls = registry.get(args.explain)
        if cls is None:
            print(f"unknown rule {args.explain!r}; known: "
                  f"{', '.join(sorted(registry))}", file=sys.stderr)
            return 2
        print(f"{cls.name} — {cls.summary}\n")
        print(cls.doc)
        return 0

    import os
    paths = args.paths or [p for p in _DEFAULT_PATHS if os.path.isdir(p)]
    if not paths:
        print("no paths given and none of "
              f"{', '.join(_DEFAULT_PATHS)} exist here", file=sys.stderr)
        return 2
    rule_names = ([r.strip() for r in args.rules.split(",") if r.strip()]
                  if args.rules else None)
    try:
        result = run_paths(paths, rule_names=rule_names,
                           suppressions_path=args.suppressions)
    except KeyError as e:
        print(e.args[0], file=sys.stderr)
        return 2
    print(report_text(result) if args.format == "text"
          else report_json(result))
    return 0 if result.ok else 1


if __name__ == "__main__":
    sys.exit(main())
