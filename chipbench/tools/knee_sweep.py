"""The knee of a serving cell, found once by a sweep on the chip:

    python -m chipbench.tools.knee_sweep --workload <cell> --seed 1 \
        --rates 100,200,400,... --seconds 6

One engine, set up once; one open-loop window per offered rate, each on
sessions of its own. Prints one JSON line per rate: offered and completed
requests per second, p50 / p95 from due time, the generator's lateness and
failures. The knee is the highest rate at which completions still follow
the offered rate, nothing fails and the generator is not late; the cell's
fixed rate is 0.8 of it (PERF.md has the table). Not run by the benchmark.
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.tools.knee_sweep")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args(argv)
    from chipbench.harness import common, loadgen
    from chipbench.harness import serve_window as sw
    _, config_doc, traffic, _, device = common.open_cell(args.workload,
                                                         ".sweep")
    cfg = common.build_config(config_doc, traffic, args.seed)
    rates = [float(r) for r in args.rates.split(",")]
    total = int(sum(rates) * args.seconds)
    engine, sessions, cold_failed = sw.start_engine(cfg, traffic, args.seed,
                                                    total)
    for i, rate in enumerate(rates):
        due = loadgen.arrival_times(args.seed + i, rate, args.seconds)
        gen = loadgen.OpenLoop(engine, sessions, due, args.seed + i)
        t0 = gen.run()
        answered = gen.wait_idle(60.0)
        t1 = t0 + args.seconds
        lat = gen.latency_ms + [float("inf")] * gen.failed
        print(json.dumps({
            "rate": rate, "attempted": gen.attempted, "failed": gen.failed,
            "completed_per_s": sum(t <= t1 for t in gen.done_at) / args.seconds,
            "p50_ms": common.percentile(lat, 50),
            "p95_ms": common.percentile(lat, 95),
            "late_p95_ms": common.percentile(gen.late_ms, 95),
            "drained_s_after_close": max(gen.done_at) - t1 if gen.done_at else None,
            "answered": answered, "device": device["kind"]}), flush=True)
    engine.stop(drain=False)
    print(json.dumps({"memory_peak_bytes": common.memory_peak_bytes(),
                      "cold_failed": cold_failed}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
