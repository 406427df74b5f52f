#!/usr/bin/env python3
"""Record a few engine steps of a cell under the profiler, reduced to the
JSON that the trace readers' tests read; not a run of the benchmark.

    python3 benchmarks/chip/record_trace.py --workload <cell> --seed <n> \
        --steps 3 --out trace_<cell>_engine.json

Set-up is a run's (weights, engine, warm-ups, a closed loop's first
prefills); then ``--steps`` engine steps run inside a ``window`` span
with the profiler on. The file holds the host spans and the device's
program runs of those steps, its ops of 20 us and longer, the decode
program's op names (compiled with metadata in the cache key, so that
its scopes survive the persistent cache), the run record the readers
use, and the engine's counters over the steps.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

import devtrace
import enginetrace
import run

MIN_OP_NS = 20_000
COUNTERS = ("expert_rows", "experts_hit", "alloc_token_steps",
            "ring_alloc_token_steps", "live_token_steps")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run.check_device(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

    h = run.Harness(cell, args.seed, seconds=1.0)
    live = {}
    h.warm()
    if h.rec.loop == "closed":
        h.prime_closed(live)
    eng, rec = h.eng, h.rec
    before = {k: getattr(eng.stats, k, 0) for k in COUNTERS}
    tdir = tempfile.mkdtemp(prefix="chip_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tdir, profiler_options=opts)
    rec.t_open = time.perf_counter()
    with TraceAnnotation("window"):
        for _ in range(args.steps):
            h._step(live)
    rec.t_close = time.perf_counter()
    jax.profiler.stop_trace()
    counters = {k: getattr(eng.stats, k, 0) - before[k] for k in COUNTERS}
    hlo = eng._step.lower(eng.params, eng.cache,
                          jnp.asarray(eng.last_tokens)).compile().as_text()

    trace = devtrace.load(tdir)
    shutil.rmtree(tdir, ignore_errors=True)
    w0, w1 = devtrace._window(trace["host"])
    devices = {}
    for name, d in trace["devices"].items():
        devices[name] = {
            "ops": [e for e in devtrace._clip(d["ops"], w0, w1)
                    if e[1] - e[0] >= MIN_OP_NS],
            "modules": devtrace._clip(d["modules"], w0, w1)}
    kept = {enginetrace.instruction(n) for d in devices.values()
            for _, _, n in d["ops"]}
    names = {k: v for k, v in enginetrace.op_names(hlo).items()
             if k in kept}
    steps = [s for s in rec.steps if s.t0 >= rec.t_open]
    rids = {r for s in steps for r in list(s.served) + s.admitted}
    record = {
        "t_open": rec.t_open, "t_close": rec.t_close,
        "steps": [{"t0": s.t0, "t1": s.t1, "admitted": s.admitted,
                   "served": s.served, "contexts": s.contexts}
                  for s in steps],
        "requests": {r: {"prompt_len": rec.requests[r].prompt_len}
                     for r in sorted(rids)},
        "peak": run.peaks.peaks(jax.devices()[0].device_kind),
        "counters": counters}
    out = {"host": [e for e in trace["host"] if w0 <= e[0] <= w1],
           "devices": devices, "step_names": names, "record": record}
    with open(args.out, "w") as f:
        json.dump(out, f)
    print(json.dumps({"out": args.out, "steps": len(steps),
                      "counters": counters}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
