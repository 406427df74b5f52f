#!/usr/bin/env python3
"""Knee sweep of an open-loop cell: the highest offered rate that the
system sustains; not a run of the benchmark.

    python3 benchmarks/chip/sweep.py --workload <cell> --seconds <s> \
        --seed <n> --rates 3,4,5,6

For each rate, in this one process, the cell's traffic at that rate for
one window: requests due and completed, the backlog (due but not
finished) at the window's middle and at its close, TTFT p50/p90 and
TPOT p95. Below the knee the backlog at the close is about what it was
at the middle; past it the queue grows through the window, and the
backlog and TTFT with it. Each rate is one JSON line on standard output.
"""
from __future__ import annotations

import argparse
import json
import sys

import run
import timing


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit("a knee sweep needs an open-loop cell")
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.traffic = dict(cell.traffic, rate_rps=rate)
        rec = run.measure(cell, args.seed, args.seconds, trace=False)
        due = timing.due_in_window(rec)
        mid = rec.t_open + rec.seconds / 2

        def backlog(t):
            return sum(r.due < t and not (len(r.tokens) == r.out_len
                                          and r.times[-1] <= t)
                       for r in due)
        done = [r for r in due if len(r.tokens) == r.out_len
                and r.times[-1] <= rec.t_close]
        print(json.dumps({
            "rate_rps": rate, "due": len(due), "completed": len(done),
            "backlog_mid": backlog(mid), "backlog": backlog(rec.t_close),
            "completed_rps": len(done) / rec.seconds,
            "ttft_p50_ms": timing.percentile_ms(timing.ttft_s(rec), 50),
            "ttft_p90_ms": timing.percentile_ms(timing.ttft_s(rec), 90),
            "tpot_p95_ms": timing.percentile_ms(timing.tpot_s(rec), 95),
            "steps": len(timing.window_steps(rec))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
