"""Readings that set a cell's correctness limit, in one process:

    python3 -m chipbench.control --workload <cell> --seeds 1,2,3 \\
        --seconds 20 --precision fp8

For each seed it runs the cell as the benchmark does (its own load, a
short window) and prints one JSON line with the program's widest logit
gap and the control's: the plain reference computed at ``--precision``,
the step below the configuration's bf16, put in the program's place.
The control is what the run compares, so its ``correct`` has to read
false.
The limit in ``limits/<cell>.json`` lies between the largest program
reading and the smallest control reading.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--precision", default="fp8",
                    choices=("fp8", "int8"))
    a = ap.parse_args(argv)
    from chipbench import harness, spec
    sys.path.insert(0, str(spec.REPO / "src"))
    dev = harness.device_info()
    if dev["platform"] != "tpu":
        print(f"control: needs a TPU, found {dev['platform']}",
              file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    bench = spec.Layout().bench()
    for seed in (int(s) for s in a.seeds.split(",")):
        t0 = time.monotonic()
        r = harness.run_cell(bench, a.workload, seed, a.seconds, False, t0,
                             control=a.precision,
                             log=lambda *x: print(*x, file=sys.stderr))
        print(json.dumps({
            "workload": a.workload, "seed": seed,
            "program": r["control"]["program_widest_logit_gap"],
            "control": r["checks"]["widest_logit_gap"]["value"],
            "limit": r["checks"]["widest_logit_gap"]["limit"],
            "correct": r["correct"],
            "precision": a.precision,
            "served_tokens": r["control"]["served_tokens"],
            "failed": r["failed"], "seconds": time.monotonic() - t0}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
