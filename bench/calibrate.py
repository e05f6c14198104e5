"""Readings for the correctness limits, at a cell's own size, in one
process so that the set-up's compiles are shared.  Each run is a whole
run of the cell, judged by the comparison that decides ``correct``, with
one of these in the program's place:

* ``program``: the program as it is (its widest served-token gap);
* ``control``: the plain reference in fp8, one step below the
  configuration's bfloat16, read at the same positions (the program's
  own gap is printed beside it);
* a fault of `bench/faults.py` planted under the timed path.

    python3 bench/calibrate.py --workload olmo-1b.decode --seconds 5 \\
        --runs control:11 control:12 altered_token:13

Prints one JSON line per run: ``{"mode", "seed", "max_logit_gap",
"limit", "correct", ...}``.  The benchmark's own runs run none of these.
Needs a TPU, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys

from run import ROOT, free_device_memory, run_cell, use_compile_cache

from bench import faults


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--runs", nargs="+", required=True,
                    help="MODE:SEED, MODE one of program, control, "
                    + ", ".join(faults.FAULTS))
    args = ap.parse_args(argv)
    runs = [(m, int(s)) for m, s in (r.split(":") for r in args.runs)]
    for mode, _ in runs:
        if mode not in ("program", "control", *faults.FAULTS):
            ap.error(f"unknown mode {mode!r}")
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[calibrate] no TPU: JAX sees {dev.platform}", file=sys.stderr)
        return 2
    use_compile_cache()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for mode, seed in runs:
        plant = faults.planted(mode) if mode in faults.FAULTS \
            else contextlib.nullcontext()
        with plant:
            r = run_cell(bench, args.workload, seed, args.seconds, False,
                         dev.device_kind, 1, control=mode == "control")
        c = r["checks"]["max_logit_gap"]
        print(json.dumps({"mode": mode, "seed": seed,
                          "max_logit_gap": c["value"], "limit": c["limit"],
                          "correct": r["correct"],
                          **({"program_max_logit_gap":
                              r["program_max_logit_gap"]}
                             if mode == "control" else {}),
                          "metrics": r["metrics"]}), flush=True)
        free_device_memory()
    return 0


if __name__ == "__main__":
    sys.exit(main())
