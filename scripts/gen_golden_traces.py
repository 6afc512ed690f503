#!/usr/bin/env python
"""Regenerate (or --check) the committed golden-trace fixtures.

``python scripts/gen_golden_traces.py``            regenerates, for every
pinned case of `repro.sim.trace.golden.CASES`, each family's pinned
micro-trace under ``tests/fixtures/<case directory>/`` (one compile + one
tiny traced run per family; ``--only NAME [NAME...]`` restricts to some
families).

``python scripts/gen_golden_traces.py --check`` is the cheap CI guard:
no simulation, just the structural freshness check from
`repro.sim.trace.golden.check_fixtures` over every case — every family
has a fixture, no orphans, and each fixture's pinned parameters / channel
layout match the code. Bit-level identity of a re-run against each
fixture is asserted by the tier-1 test ``tests/test_golden_traces.py``.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="verify fixture freshness structurally; exit 1 "
                         "on any problem (no simulation)")
    ap.add_argument("--only", nargs="+", metavar="NAME",
                    help="regenerate only these families")
    ap.add_argument("--out", default=None,
                    help="fixture root (default: the committed "
                         "tests/fixtures/)")
    args = ap.parse_args(argv)

    from repro.sim.trace import golden

    if args.check:
        problems = golden.check_fixtures(args.out)
        for p in problems:
            print(f"STALE: {p}")
        print(f"golden traces: {'FRESH' if not problems else 'STALE'} "
              f"({len(problems)} problem(s))")
        return 1 if problems else 0

    from repro.sim.config import PRESETS
    names = args.only or sorted(PRESETS)
    unknown = [n for n in names if n not in PRESETS]
    if unknown:
        print(f"unknown families {unknown}; have {sorted(PRESETS)}")
        return 2
    for case in golden.CASES:
        for name in golden.families(case):
            if name not in names:
                continue
            fx = golden.generate_fixture(PRESETS[name], case)
            path = golden.save_fixture(
                golden.fixture_path(name, args.out, case), fx)
            kb = path.stat().st_size / 1024
            print(f"{case}/{name:<16} -> {path} ({kb:.0f} KB, "
                  f"active to tick {int(fx['active_ticks'][0])})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
