"""Print what a profiler trace holds: every plane, its lines with their
event counts, and the longest-running event names of each line.

    python3 bench/tools/inspect_trace.py .bench_trace [--top 15]
"""
from __future__ import annotations

import argparse
import pathlib
import sys
from collections import defaultdict

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import trace  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path")
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args(argv)
    for p in trace.load(args.path):
        print(f"plane {p.name!r}")
        for ln, evs in p.lines.items():
            tot = defaultdict(float)
            for e in evs:
                tot[e.name] += (e.end_ns - e.start_ns) * 1e-9
            span = (min(e.start_ns for e in evs), max(e.end_ns for e in evs)) \
                if evs else (0, 0)
            print(f"  line {ln!r}: {len(evs)} events, {span[0]!r}..{span[1]!r} ns")
            for name, s in sorted(tot.items(), key=lambda kv: -kv[1])[:args.top]:
                print(f"    {s!r:>24} s  {name[:160]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
