"""`python -m photon_ml_tpu.analysis` — run photon-lint.

Exit status: 0 clean, 1 findings, 2 usage error — so the module works
unmodified as a pre-commit hook or CI gate. Mirrors the introspection
convention of `python -m photon_ml_tpu.utils.faults --list-sites` and
`python -m photon_ml_tpu.utils.knobs --table`.
"""

from __future__ import annotations

import argparse
import sys
from typing import List, Optional

from photon_ml_tpu.analysis import CHECKS, run_checks


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m photon_ml_tpu.analysis",
        description=(
            "photon-lint: AST-checked repo invariants (knobs, fault "
            "sites, jit purity, thread lifecycle, buffer donation, "
            "contract keys)."
        ),
    )
    p.add_argument(
        "paths",
        nargs="*",
        help="files/directories to analyze (default: the live tree — the "
        "package and tests/)",
    )
    p.add_argument(
        "--list-checks",
        action="store_true",
        help="print every registered check and exit",
    )
    p.add_argument(
        "--check",
        action="append",
        metavar="NAME",
        help="run only this check (repeatable)",
    )
    return p


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    if args.list_checks:
        width = max(len(n) for n in CHECKS)
        for name in sorted(CHECKS):
            print(f"{name.ljust(width)}  {CHECKS[name].description}")
        return 0
    try:
        findings = run_checks(paths=args.paths or None, checks=args.check)
    except KeyError as exc:
        print(f"error: {exc.args[0]}", file=sys.stderr)
        return 2
    for f in findings:
        print(f.render())
    n = len(findings)
    scope = "given paths" if args.paths else "live tree"
    if n:
        print(f"photon-lint: {n} finding(s) on the {scope}", file=sys.stderr)
        return 1
    print(f"photon-lint: clean ({scope})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
