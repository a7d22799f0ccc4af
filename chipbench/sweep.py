"""Find the knee of an open-loop mix once, by a sweep of fixed rates on the
chip: one process builds the model once and serves the mix at each rate.

    python3 -m chipbench.sweep --config <name> --traffic <mix> \\
        --rates 0.5,1,2 --seconds 30 --seed <n>

Per rate it prints one JSON line: TTFT p50/p90 of the requests due in
the first and second half of the window (a queue that grows shows as a
second half slower than the first), the gaps between tokens, the
seconds the engine needed past the window to finish, and failures. The
knee is the highest rate whose second half keeps up with its first.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args(argv)
    from chipbench import harness, spec, traffic, weights, work
    sys.path.insert(0, str(spec.REPO / "src"))
    from repro.serving import Engine, EngineConfig, Request
    dev = harness.device_info()
    if dev["platform"] != "tpu":
        print(f"sweep: needs a TPU, found {dev['platform']}",
              file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    lay = spec.Layout()
    bench = lay.bench()
    cfg = lay.config(bench, a.config)
    mix = lay.mix(a.traffic)
    arch = importlib.import_module(f"chipbench.archs.{cfg['arch']}")
    params = arch.program_params(cfg, weights.root_key(a.seed))
    e = mix["engine"]
    for rate in (float(r) for r in a.rates.split(",")):
        m = dict(mix, rate_per_s=rate)
        engine = Engine(arch.program_config(cfg), params, EngineConfig(
            n_slots=e["n_slots"], n_blocks=e["n_blocks"],
            block_size=e["block_size"], max_len=e["max_len"],
            prefill_chunk=e["prefill_chunk"]))
        engine.compile()
        specs = traffic.generate(m, a.seed, a.seconds, cfg["vocab_size"])
        reqs = [Request(rid=s.rid, prompt=s.prompt, max_new=s.max_new,
                        arrival=s.arrival, deadline=s.deadline)
                for s in specs]
        lo = float(m["lead_in_s"])
        hi = lo + a.seconds
        probe = harness.Probe(engine, lo, hi)
        t0 = time.monotonic()
        engine.run(reqs, clock="wall")
        probe.finish()
        end = time.monotonic() - t0
        row = {"rate_per_s": rate, "requests": sum(s.counted
                                                    for s in specs)}
        mid = lo + a.seconds / 2
        for half, keep in (("first", lambda s: s.arrival < mid),
                           ("second", lambda s: s.arrival >= mid)):
            ttft = [r.ttft * 1e3 for r, s in zip(reqs, specs)
                    if s.counted and keep(s) and r.ttft is not None]
            if ttft:
                row[f"ttft_p50_ms.{half}"] = work.percentile(ttft, 50)
                row[f"ttft_p90_ms.{half}"] = work.percentile(ttft, 90)
        ttft, gaps = harness.ttft_and_gaps(reqs, specs, lo, hi)
        if gaps:
            row["itl_p50_ms"] = work.percentile(gaps, 50)
            row["itl_p95_ms"] = work.percentile(gaps, 95)
        row["drain_s"] = end - hi
        row["failed"] = harness.attempted_failed(reqs, specs)[1]
        row["unserved"] = sum(s.counted and r.ttft is None
                              for r, s in zip(reqs, specs))
        row["rows_per_step"] = (sum(p.rows for p in probe.plans)
                                / max(1, len(probe.plans)))
        row["prefill_step_share"] = (
            100.0 * sum(p.c > 1 for p in probe.plans)
            / max(1, len(probe.plans)))
        secs, steps = probe.record_window()
        row["ms_per_step"] = 1e3 * secs / max(1, steps)
        print(json.dumps(row), flush=True)
        del engine, probe
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
