"""Run one benchmark cell on the accelerator and print its result line.

    python3 -m chipbench.run --workload <cell> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

Run from the directory that holds ``BENCHMARK.json``. The program under
test is imported from ``src/`` beside it. Without a TPU, or with fewer
chips than the cell asks for, it prints no result and exits non-zero.
Log lines go to standard error, ending with each compared number beside
its limit; the last line of standard output is the result as JSON.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def check_lines(checks: dict):
    return [f"check {k}: {v['value']!r} (limit {v['limit']!r})"
            for k, v in checks.items()]


def main(argv=None) -> int:
    args = parse_args(argv)
    from chipbench import harness, spec
    src = spec.REPO / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    bench = spec.Layout().bench()
    wl = spec.workload(bench, args.workload)
    import jax
    dev = harness.device_info()
    if dev["platform"] != "tpu" or dev["count"] < wl["chips"]:
        log(f"chipbench: {args.workload} needs {wl['chips']} TPU chip(s); "
            f"JAX found {dev['count']} {dev['platform']!r} device(s)")
        return 2
    log(f"device: {dev['kind']} x{dev['count']}; compile cache "
        f"{harness.enable_compile_cache()}; jax {jax.__version__}")
    result = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START, log=log)
    for line in check_lines(result["checks"]):
        log(line)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
