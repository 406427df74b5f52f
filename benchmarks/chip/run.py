#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json``, one run, one line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A cell names a configuration (``configs/<config>.json``) and a traffic
mix (``traffic/<traffic>.json``); every metric is read by
``metrics/<name>.py`` (or, for a name with a suffix such as
``decode_step_ms.serve``, by ``metrics/decode_step_ms.py``). So a cell,
a configuration, a traffic mix or a metric is added as files, found by
name.

One process holds the chip. It makes the weights on the device from the
seed, builds the launcher's own serving engine
(``repro.launch.serve.build_engine``: paged KV cache, the launcher's
default kernel policy, the launcher's serving dtype), warms every shape
the cell's traffic uses, then measures for ``--seconds``. With
``--trace 1`` the window runs under the profiler and the per-layer
metrics are reported; with ``--trace 0`` the end-to-end metrics. After
the window it frees the engine and checks what the window served against
a plain float32 reference (the module that the configuration file names
under ``reference``, beside it in ``configs/``); the number compared and
its limit are the last lines on standard error and the last key of the
result. The last line of standard output is the JSON result.

Without an accelerator, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))

import peaks  # noqa: E402
import traffic as traffic_gen  # noqa: E402

WARM_SEED = 0          # warm-up prompts are the same in every run
WARM_OUT = 2           # tokens per warm-up request
CLOSED_ITEMS = 64      # closed-loop requests made (then reused in turn)


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------- cells
@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    metrics: List[Dict]          # this cell's entries, with "_kind"


def load_cell(name: str) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / cfgs[w["config"]]["file"]).read_text())
    tr = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = []
    for m in bench["end_to_end"]:
        if "workloads" not in m or name in m["workloads"]:
            e2e.append(dict(m, _kind="end_to_end"))
    reported = {m["name"] for m in e2e}
    layer = []
    for m in bench["per_layer"]:
        if ("workloads" in m and name in m["workloads"]) or (
                "workloads" not in m and m["moves"] in reported):
            layer.append(dict(m, _kind="per_layer"))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=tr, metrics=e2e + layer)


@functools.lru_cache(maxsize=None)
def _load(path: Path, name: str):
    """A module of the benchmark's own, loaded once per process."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> Callable:
    """``metrics/<name>.py``, else ``metrics/<name up to its first
    dot>.py``: the module's ``read(run)`` returns the value or None."""
    for stem in (name, name.split(".")[0]):
        path = HERE / "metrics" / f"{stem}.py"
        if path.exists():
            return _load(path, f"chip_metric_{stem.replace('.', '_')}").read
    raise FileNotFoundError(f"no reader for metric {name!r} under "
                            f"{HERE / 'metrics'}")


def architecture(config: Dict):
    """``configs/<reference>.py`` for a configuration file: its plain
    reference, ``ModelCosts`` and ``check_registry``."""
    name = config["reference"]
    return _load(HERE / "configs" / f"{name}.py", f"chip_arch_{name}")


def param_seed(seed: int) -> int:
    """A 31-bit weight seed from any whole number (the program's key
    takes 32 bits and would fold larger seeds onto smaller ones)."""
    h = hashlib.blake2b(str(int(seed)).encode(), digest_size=4).digest()
    return int.from_bytes(h, "little") >> 1


# ------------------------------------------------------------------ record
@dataclass
class ReqRecord:
    rid: int
    due: float                   # host clock
    prompt: np.ndarray
    out_len: int
    submit: float = float("nan")
    times: List[float] = field(default_factory=list)   # per served token
    tokens: List[int] = field(default_factory=list)
    rejected: bool = False
    slot: int = -1               # the engine slot it was admitted to

    @property
    def prompt_len(self) -> int:
        return int(len(self.prompt))


@dataclass
class StepRecord:
    t0: float
    t1: float
    admitted: List[int]          # rids prefilled in this step
    served: Dict[int, int]       # rid -> tokens it got in this step
    contexts: List[int]          # decode contexts, new token included


@dataclass
class RunRecord:
    """Everything a metric reader may read."""

    cell: Cell
    seed: int
    seconds: float
    loop: str
    t_open: float = 0.0
    t_close: float = 0.0
    setup_s: float = 0.0
    setup_parts: Dict[str, float] = field(default_factory=dict)
    requests: Dict[int, ReqRecord] = field(default_factory=dict)
    steps: List[StepRecord] = field(default_factory=list)
    prefill_calls: int = 0       # EngineStats deltas over the window
    prefill_tokens: int = 0
    window_compiles: int = 0
    model: Optional[object] = None       # the architecture's ModelCosts
    peak: Dict[str, float] = field(default_factory=dict)
    device: Dict = field(default_factory=dict)
    trace: Optional[Dict] = None


class CompileCounter:
    """Programs compiled, split by phase: ``lowerings`` counts every
    program that the in-memory caches did not hold, ``cache_hits`` those
    of them that the persistent cache served."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        from jax import monitoring
        self.phase = "setup"
        self.counts: Dict[str, int] = {}
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _count(self, kind: str):
        key = f"{self.phase}.{kind}"
        self.counts[key] = self.counts.get(key, 0) + 1

    def _on_duration(self, event, duration, **_):
        if event == self.LOWER:
            self._count("lowerings")

    def _on_event(self, event, **_):
        if event == self.HIT:
            self._count("cache_hits")

    def get(self, phase: str, kind: str) -> int:
        return self.counts.get(f"{phase}.{kind}", 0)

    def compiled(self, phase: str) -> int:
        """Programs of ``phase`` that neither cache held."""
        return self.get(phase, "lowerings") - self.get(phase, "cache_hits")

    def close(self):
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)


class GcWatch:
    """Python's garbage collections inside the window: how many of each
    generation, and how long they held the host."""

    def __init__(self):
        self.on = False
        self.t = 0.0
        self.pauses: Dict[int, List[float]] = {0: [], 1: [], 2: []}
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self.t = time.perf_counter()
        else:
            self.pauses[info["generation"]].append(
                time.perf_counter() - self.t)

    def summary(self) -> str:
        every = [x for v in self.pauses.values() for x in v]
        return (f"{len(every)} collections (by generation "
                f"{[len(v) for v in self.pauses.values()]}), "
                f"{1e3 * sum(every):.3f} ms in all, longest "
                f"{1e3 * max(every, default=0.0):.3f} ms")

    def close(self):
        gc.callbacks.remove(self._cb)


def check_device(chips: int):
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoDevice(f"JAX found no accelerator: {devs[0]}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    return devs


# ------------------------------------------------------------------ engine
class Harness:
    """Builds the engine for a cell, warms it, drives the window."""

    def __init__(self, cell: Cell, seed: int, seconds: float):
        from repro.configs import get_arch
        from repro.launch.serve import (build_engine, init_serving_params,
                                        serve_dtype, serving_runtime)

        c = cell.config
        self.serve = c["serve"]
        self.model = c["model"]
        arch = architecture(c)
        self.cfg = arch.program_config(get_arch(c["arch"]), self.model)
        dtype = serve_dtype()
        if dtype != self.serve["dtype"]:
            raise SystemExit(f"the launcher serves in {dtype}; the "
                             f"configuration states {self.serve['dtype']}")
        self.rec = RunRecord(cell=cell, seed=seed, seconds=seconds,
                             loop=cell.traffic["loop"])
        self.rec.model = arch.ModelCosts(self.model)
        parts = self.rec.setup_parts
        # imports, the runtime's start and the device check
        parts["start"] = time.perf_counter() - T_START

        t = time.perf_counter()
        self.params = init_serving_params(self.cfg, param_seed(seed), dtype)
        jax.block_until_ready(self.params)
        parts["params"] = time.perf_counter() - t

        t = time.perf_counter()
        self.rt = serving_runtime(dtype)
        self.eng = build_engine(
            self.params, self.cfg, self.rt, n_slots=self.serve["n_slots"],
            max_len=self.serve["max_len"], buckets=self.serve["buckets"],
            page_size=self.serve["page_size"],
            page_budget=self.serve["page_budget"])
        jax.block_until_ready(self.eng.cache)
        parts["engine"] = time.perf_counter() - t
        self.items = self._items(cell, seed, seconds)

    def _items(self, cell: Cell, seed: int, seconds: float):
        tr, vocab = cell.traffic, self.cfg.vocab_size
        if tr["loop"] == "open":
            items = traffic_gen.open_loop(tr, seconds, seed, vocab)
        elif tr["loop"] == "closed":
            items = traffic_gen.closed_loop(tr, seed, vocab, CLOSED_ITEMS)
        else:
            raise SystemExit(f"unknown loop {tr['loop']!r}")
        traffic_gen.check_fits(items, self.serve["max_len"])
        return items

    # ---------------------------------------------------------------- setup
    def _request(self, rid: int, it, due: float):
        from repro.serve import Request
        r = ReqRecord(rid=rid, due=due, prompt=it.prompt,
                      out_len=it.out_len)
        self.rec.requests[rid] = r
        return r, Request(rid=rid, prompt=it.prompt,
                          max_new_tokens=it.out_len)

    def warm(self):
        """Admit and finish one request of every prefill bucket this
        cell's traffic uses: the prefill, page scatter, splice, decode
        and release programs all compile here, not in the window."""
        from repro.serve import Request
        sched = self.eng.scheduler
        buckets = sorted({sched.plan(len(it.prompt)).prefill_len
                          for it in self.items})
        rng = np.random.default_rng(WARM_SEED)
        for i, b in enumerate(buckets):
            t = time.perf_counter()
            n = min(b, self.serve["max_len"] - WARM_OUT)   # pads to b
            self.eng.submit(Request(
                rid=-1 - i, max_new_tokens=WARM_OUT,
                prompt=rng.integers(0, self.cfg.vocab_size, n)
                .astype(np.int32)))
            while self.eng.queue or any(s is not None
                                        for s in self.eng.slots):
                self.eng.step()
            jax.block_until_ready(self.eng.cache)
            self.rec.setup_parts[f"warm_{b}"] = time.perf_counter() - t
        self.eng.finished.clear()

    # --------------------------------------------------------------- window
    def _step(self, live: Dict[int, tuple]):
        """One engine step under a span; records its tokens and times."""
        before = {rid: len(req.out_tokens) for rid, (_, req) in live.items()}
        t0 = time.perf_counter()
        with TraceAnnotation("engine.step"):
            self.eng.step()
        t1 = time.perf_counter()
        admitted, served, contexts, done = [], {}, [], []
        for rid, (r, req) in live.items():
            new = req.out_tokens[before[rid]:]
            if not new:
                continue
            served[rid] = len(new)
            if before[rid] == 0:
                admitted.append(rid)
                r.slot = next((i for i, s in enumerate(self.eng.slots)
                               if s is req), -1)
            r.tokens.extend(new)
            r.times.extend([t1] * len(new))
            contexts.append(r.prompt_len + len(req.out_tokens) - 1)
            if req.done:
                done.append(rid)
        for rid in done:
            del live[rid]
        self.rec.steps.append(StepRecord(t0, t1, admitted, served,
                                          contexts))
        return done

    def _submit(self, r: ReqRecord, req, live):
        r.submit = time.perf_counter()
        n_rej = len(self.eng.rejected)
        self.eng.submit(req)
        if len(self.eng.rejected) > n_rej:
            r.rejected = True
        else:
            live[r.rid] = (r, req)

    def prime_closed(self, live):
        """Closed loop: the first request of every slot is admitted in
        set-up, so the window opens with a full batch."""
        t = time.perf_counter()
        n = self.serve["n_slots"]
        for it in self.items[:n]:
            r, req = self._request(it.rid, it, due=t)
            self._submit(r, req, live)
        while self.eng.queue:
            self._step(live)
        jax.block_until_ready(self.eng.cache)
        self.rec.setup_parts["first_prefills"] = time.perf_counter() - t
        return n

    def settle(self):
        """The last step of set-up: a full collection, then every object
        that set-up made (modules, weights' handles, the engine) is
        frozen out of later collections, which in the window scan only
        what the window makes."""
        t = time.perf_counter()
        n = len(gc.get_objects())
        gc.collect()
        gc.freeze()
        self.rec.setup_parts["gc"] = time.perf_counter() - t
        log(f"gc: set-up's full collection over {n} objects took "
            f"{1e3 * self.rec.setup_parts['gc']:.3f} ms; "
            f"{gc.get_freeze_count()} frozen")

    def window(self, counter: CompileCounter, live, next_item: int):
        rec, eng = self.rec, self.eng
        s0 = (eng.stats.prefills, eng.stats.prefill_tokens)
        counter.phase = "window"
        rec.t_open = time.perf_counter()
        rec.t_close = rec.t_open + rec.seconds
        with TraceAnnotation("window"):
            if rec.loop == "open":
                self._open(live)
            else:
                self._closed(live, next_item)
        counter.phase = "after"
        rec.prefill_calls = eng.stats.prefills - s0[0]
        rec.prefill_tokens = eng.stats.prefill_tokens - s0[1]
        rec.window_compiles = counter.get("window", "lowerings")

    def _open(self, live):
        rec, eng = self.rec, self.eng
        pending = [(rec.t_open + it.due, it) for it in self.items]
        i = 0
        while True:
            now = time.perf_counter()
            if now >= rec.t_close:
                # due but never handed to the engine: they count too
                for due, it in pending[i:]:
                    if due < rec.t_close:
                        self._request(it.rid, it, due)
                return
            with TraceAnnotation("generator.submit"):
                while i < len(pending) and pending[i][0] <= now:
                    it = pending[i][1]
                    r, req = self._request(it.rid, it, pending[i][0])
                    self._submit(r, req, live)
                    i += 1
            if eng.queue or live:
                self._step(live)
            else:
                nxt = pending[i][0] if i < len(pending) else rec.t_close
                with TraceAnnotation("generator.wait"):
                    time.sleep(max(0.0, min(nxt, rec.t_close) - now))

    def _closed(self, live, next_item: int):
        rec = self.rec
        while time.perf_counter() < rec.t_close:
            done = self._step(live)
            with TraceAnnotation("generator.submit"):
                for _ in done:      # each finished client sends its next
                    it = self.items[next_item % len(self.items)]
                    r, req = self._request(next_item, it,
                                           due=time.perf_counter())
                    self._submit(r, req, live)
                    next_item += 1

    def free(self):
        """Drop the engine's device state before the reference runs."""
        self.eng.cache = None
        self.eng.params = None
        self.eng = None
        self.params = None
        gc.unfreeze()
        gc.collect()


# ------------------------------------------------------------- correctness
def pick_sample(rec: RunRecord, seed: int, tokens: int) -> List[ReqRecord]:
    """Requests finished in the window: the longest; then, unless it
    held one, one that held a slot in the upper half of the engine's
    slots; then others, in an order drawn from the seed, until
    ``tokens`` served tokens."""
    done = [r for r in rec.requests.values()
            if len(r.tokens) == r.out_len and r.times
            and r.times[-1] <= rec.t_close]
    if not done:
        return []
    upper = rec.cell.config["serve"]["n_slots"] // 2
    done.sort(key=lambda r: (-(r.prompt_len + r.out_len), r.rid))
    out, rest = [done[0]], done[1:]
    order = [rest[j] for j in np.random.default_rng(seed)
             .permutation(len(rest))]
    if out[0].slot < upper:
        high = next((r for r in order if r.slot >= upper), None)
        if high is not None:
            out.append(high)
            order.remove(high)
    for r in order:
        if sum(len(x.tokens) for x in out) >= tokens:
            break
        out.append(r)
    return out


def check_served(rec: RunRecord, seed: int,
                 control: Optional[str] = None) -> Dict:
    """The widest reference-logit gap of the sample's served tokens."""
    c = rec.cell.config
    reference = architecture(c)
    sample = pick_sample(rec, seed, c["correct"]["sample_tokens"])
    t = time.perf_counter()
    w = reference.make_weights(c["model"], param_seed(seed),
                               c["serve"]["dtype"])
    gaps = [reference.served_gaps(c["model"], w, r.prompt, r.tokens,
                                  control) for r in sample]
    del w
    gap = max((float(g.max()) for g in gaps), default=float("inf"))
    return {"max_logit_gap": gap, "limit": c["correct"]["max_logit_gap"],
            "requests": len(sample),
            "slots": sorted({r.slot for r in sample}),
            "tokens": sum(len(r.tokens) for r in sample),
            "seconds": time.perf_counter() - t}


# ------------------------------------------------------------------- trace
def read_trace(tdir: str, rec: RunRecord) -> Dict:
    import devtrace
    return devtrace.summarize(devtrace.load(tdir), rec)


# --------------------------------------------------------------------- run
def measure(cell: Cell, seed: int, seconds: float, trace: bool,
            device_check: bool = True) -> RunRecord:
    """Set-up and the window of one run; the engine is freed (and the
    trace read) before it returns."""
    devs = check_device(cell.chips) if device_check else jax.devices()
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    # every program, however quick to compile, comes from the cache in a
    # run after the first
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter()
    gcw = GcWatch()
    try:
        h = Harness(cell, seed, seconds)
        rec = h.rec
        live: Dict[int, tuple] = {}
        h.warm()
        next_item = h.prime_closed(live) if rec.loop == "closed" else 0
        if trace:
            tdir = tempfile.mkdtemp(prefix="chip_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # no event per Python call
            opts.host_tracer_level = 2       # spans and jit dispatches
            jax.profiler.start_trace(tdir, profiler_options=opts)
        h.settle()
        rec.setup_s = time.perf_counter() - T_START
        ru0 = resource.getrusage(resource.RUSAGE_SELF)
        gcw.on = True
        h.window(counter, live, next_item)
        gcw.on = False
        ru1 = resource.getrusage(resource.RUSAGE_SELF)
        if trace:
            jax.profiler.stop_trace()
        dev = devs[0]
        stats = [d.memory_stats() or {} for d in devs[:cell.chips]]
        rec.device = {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devs), "memory_peak_bytes": max(
                          int(m.get("peak_bytes_in_use", 0))
                          for m in stats)}
        rec.peak = peaks.peaks(dev.device_kind) if device_check else {}
        live.clear()
        h.free()
        log(f"setup: {rec.setup_s:.3f} s; " + ", ".join(
            f"{k} {v:.3f} s" for k, v in rec.setup_parts.items()))
        log(f"compiles: setup {counter.get('setup', 'lowerings')} programs, "
            f"{counter.compiled('setup')} not in the cache; window "
            f"{rec.window_compiles} programs, {counter.compiled('window')} "
            f"not in the cache (cache {cache_dir})")
        log(f"memory: peak_bytes_in_use {rec.device['memory_peak_bytes']}, "
            f"bytes_limit {stats[0].get('bytes_limit')}")
        log(f"gc in the window: {gcw.summary()}")
        log(host_summary(rec, ru0, ru1))
        if trace:
            t = time.perf_counter()
            rec.trace = read_trace(tdir, rec)
            shutil.rmtree(tdir, ignore_errors=True)
            log(f"trace: read in {time.perf_counter() - t:.3f} s")
        return rec
    finally:
        counter.close()
        gcw.close()


def host_summary(rec: RunRecord, ru0, ru1) -> str:
    """Where the window's host time went: the longest engine step, the
    longest time between two steps, and the process's CPU seconds,
    context switches and page faults over the window."""
    steps = [s for s in rec.steps if s.t0 >= rec.t_open]
    worst = max(steps, key=lambda s: s.t1 - s.t0, default=None)
    gaps = [b.t0 - a.t1 for a, b in zip(steps, steps[1:])]
    cpu = (ru1.ru_utime - ru0.ru_utime) + (ru1.ru_stime - ru0.ru_stime)
    return (
        "host in the window: longest step "
        + (f"{1e3 * (worst.t1 - worst.t0):.3f} ms at "
           f"{worst.t0 - rec.t_open:.3f} s ({len(worst.admitted)} "
           f"admitted, {len(worst.served)} served)" if worst else "none")
        + f", longest between steps {1e3 * max(gaps, default=0.0):.3f} ms; "
        f"cpu {cpu:.3f} s; context switches "
        f"{ru1.ru_nvcsw - ru0.ru_nvcsw} voluntary, "
        f"{ru1.ru_nivcsw - ru0.ru_nivcsw} involuntary; page faults "
        f"{ru1.ru_majflt - ru0.ru_majflt} major, "
        f"{ru1.ru_minflt - ru0.ru_minflt} minor")


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        device_check: bool = True) -> Dict:
    """One run of ``cell``; returns the result object."""
    rec = measure(cell, seed, seconds, trace, device_check)
    check = check_served(rec, seed)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics:
        if m["_kind"] != kind:
            continue
        v = metric_reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    due = [r for r in rec.requests.values() if r.due <= rec.t_close]
    lag = sorted(r.submit - r.due for r in due if not math.isnan(r.submit))
    log(f"window: {len(due)} requests, {len(rec.steps)} steps, "
        f"{rec.prefill_calls} prefills; generator lag p50 "
        f"{1e3 * lag[len(lag) // 2] if lag else 0:.3f} ms, max "
        f"{1e3 * lag[-1] if lag else 0:.3f} ms")
    gaps = [b - a for r in due for a, b in zip(r.times, r.times[1:])
            if b <= rec.t_close]
    if gaps:
        log("per-gap ITL (not a metric): p50 "
            f"{1e3 * float(np.percentile(gaps, 50)):.3f} ms, p95 "
            f"{1e3 * float(np.percentile(gaps, 95)):.3f} ms over "
            f"{len(gaps)} gaps")
    log(f"reference: {check['requests']} requests, {check['tokens']} "
        f"served tokens, slots {check['slots']}, {check['seconds']:.3f} s")
    log(f"check: max_logit_gap {check['max_logit_gap']!r} limit "
        f"{check['limit']!r}")
    out = {"correct": check["max_logit_gap"] <= check["limit"],
           "attempted": len(due), "failed": sum(r.rejected for r in due),
           "metrics": metrics, "device": dict(rec.device)}
    if trace:
        out["device"]["busy_s"] = rec.trace["busy_s"]
        out["device"]["window_s"] = rec.trace["window_s"]
        out["breakdown"] = rec.trace["breakdown"]
    out["checks"] = {"max_logit_gap": {"value": check["max_logit_gap"],
                                       "limit": check["limit"]}}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        out = run(cell, args.seed, args.seconds, bool(args.trace))
    except NoDevice as e:
        log(f"no result: {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
