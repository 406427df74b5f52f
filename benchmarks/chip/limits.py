#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from; not a run of
the benchmark.

    python3 benchmarks/chip/limits.py --workload <cell> --seconds <s> \
        --seeds 1,2,3 [--fp8-seeds 1,2,3] \
        [--faults state_unchanged,half_batch --fault-seeds 4,5,6]

All in this one process, at the cell's own size and load, with a window
of ``--seconds``:

* ``sound``: a run of the cell as the benchmark makes it, and its widest
  reference-logit gap (the lower reading is the largest over seeds);
* ``fp8``: the control, the reference one precision below the
  configuration's bfloat16, on the same prompts and served tokens: the
  gap of the token that its float8 logits put first at each position;
* each of ``--faults`` (``faults.py``): a run with that fault planted
  underneath the timed path, and its widest gap.

Each reading is one JSON line on standard output.
"""
from __future__ import annotations

import argparse
import json
import sys

import faults
import run


def _list(text: str):
    return [s for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=_list, default=[])
    ap.add_argument("--fp8-seeds", type=_list, default=[])
    ap.add_argument("--faults", type=_list, default=[])
    ap.add_argument("--fault-seeds", type=_list, default=[])
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds]
    fp8 = [int(s) for s in args.fp8_seeds]

    def emit(seed, kind, check, rec):
        print(json.dumps({"workload": cell.name, "seed": seed, "kind": kind,
                          "max_logit_gap": check["max_logit_gap"],
                          "limit": check["limit"],
                          "requests": check["requests"],
                          "tokens": check["tokens"],
                          "slots": check["slots"],
                          "finished": sum(len(r.tokens) == r.out_len
                                          for r in rec.requests.values()),
                          "peak_bytes": rec.device["memory_peak_bytes"]}),
              flush=True)

    for seed in sorted(set(seeds) | set(fp8)):
        rec = run.measure(cell, seed, args.seconds, trace=False)
        if seed in seeds:
            emit(seed, "sound", run.check_served(rec, seed), rec)
        if seed in fp8:
            emit(seed, "fp8", run.check_served(rec, seed, control="fp8"),
                 rec)
    for kind in args.faults:
        for seed in [int(s) for s in args.fault_seeds]:
            with faults.planted(kind):
                rec = run.measure(cell, seed, args.seconds, trace=False)
            emit(seed, kind, run.check_served(rec, seed), rec)
    return 0


if __name__ == "__main__":
    sys.exit(main())
