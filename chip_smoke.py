#!/usr/bin/env python3
"""Chip smoke: serve full-width minicpm-2b on a TPU through the
launcher's own engine constructor, and check what comes out.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # four chips, sharded path only

One chip runs three phases in this one process (a chip belongs to one
process at a time):

  (a) device  — JAX must report a TPU, or the smoke fails at once;
  (b) serve   — the paged engine, an explicit all-XLA kernel policy, bf16:
                every request served to its length, token ids inside
                the vocabulary, prefill compiles within the
                scheduler's bound, finite teacher-forced logits;
  (c) pallas  — the same serving with the Pallas prefill-attention,
                paged-decode-attention and RMSNorm kernels: the decode
                step must lower to Mosaic (``tpu_custom_call``), and
                its teacher-forced logits (``serve.parity``) must be
                finite and agree with (b)'s within ``PARITY_TOL``.

``--four-chips`` runs only the sharded engine on a (data=1, model=4)
mesh against the one-device paged engine on the same requests.

Weights and requests are random, made from ``SEED``. Timings and
memory are printed as observations. The last line of standard output
is the JSON result; on any failure the script exits non-zero without
printing it.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "minicpm-2b"
SEED = 0
SLOTS, MAX_LEN, PAGE = 8, 1024, 16
BUCKETS = (128, 256, 512)          # prompts of 100-500 tokens land in all 3
N_REQUESTS, MAX_NEW = 12, 32
PROMPT_LENS = (100, 500)
PARITY_STEPS = 8                   # over the first SLOTS requests
#: teacher-forced max logit deviation allowed, tighter than the serving
#: tolerance QUANT_PARITY_TOL (0.25): on a TPU v5e the sound Pallas
#: kernels read 0.079 against XLA, while a paged kernel that masks out
#: the newest KV row reads 0.276 (PERF.md, Findings)
PARITY_TOL = 0.15
PALLAS_OPS = ("prefill_attention", "paged_decode_attention", "rmsnorm")
#: the largest share of the parameter bytes one device of a 4-way
#: tensor-parallel mesh may hold (a quarter each, plus replicated leaves)
MAX_PARAM_SHARE = 0.6


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def log(msg: str):
    print(msg, flush=True)


def device_phase(want: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu", f"no TPU: JAX's first device is {d}")
    check(len(devs) >= want, f"need {want} chips, JAX found {len(devs)}")
    log(f"[a] device: {d.device_kind} x{len(devs)} ({d.platform})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def make_requests(cfg):
    import numpy as np
    from repro.serve import Request
    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, int(n))
                    .astype(np.int32), max_new_tokens=MAX_NEW)
            for i, n in enumerate(lens)]


class CompileClock:
    """Seconds JAX spends in backend compiles (an observation)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration


def serve(tag, params, cfg, rt, reqs, clock, mesh=None):
    """Serve fresh copies of ``reqs``; check every request finished to
    its length with in-vocabulary tokens. Returns (engine, outputs)."""
    import jax
    from repro.launch.serve import build_engine
    from repro.serve import Request
    eng = build_engine(params, cfg, rt, n_slots=SLOTS, max_len=MAX_LEN,
                       buckets=BUCKETS, page_size=PAGE, mesh=mesh)
    for r in reqs:
        eng.submit(Request(rid=r.rid, prompt=r.prompt,
                           max_new_tokens=r.max_new_tokens))
    c0, t0 = clock.seconds, time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    check(not eng.rejected, f"[{tag}] rejected: "
          f"{[(r.rid, r.finish_reason) for r in eng.rejected]}")
    check(len(done) == len(reqs), f"[{tag}] served {len(done)}/{len(reqs)}")
    for r in done:
        check(r.finish_reason == "length"
              and len(r.out_tokens) == r.max_new_tokens,
              f"[{tag}] rid={r.rid} finished {r.finish_reason!r} after "
              f"{len(r.out_tokens)} tokens")
        check(all(0 <= t < cfg.vocab_size for t in r.out_tokens),
              f"[{tag}] rid={r.rid} token outside the vocabulary")
    bound = eng.scheduler.max_prefill_compiles()
    check(eng.stats.prefill_compiles <= bound,
          f"[{tag}] {eng.stats.prefill_compiles} prefill compiles > "
          f"bound {bound}")
    peak = mem_stat(jax.devices()[0], "peak_bytes_in_use")   # process
    log(f"[{tag}] served {len(done)}/{len(reqs)} requests, "
        f"{eng.stats.tokens_out} tokens, {eng.stats.steps} decode steps; "
        f"wall {wall:.3f} s (compile {clock.seconds - c0:.3f} s); "
        f"prefill compiles {eng.stats.prefill_compiles} (bound {bound}); "
        f"process peak_bytes_in_use so far {peak} on device 0")
    return eng, {r.rid: list(r.out_tokens) for r in done}


def mem_stat(device, key: str) -> int:
    """One of the device's memory counters (0 where the backend keeps
    none)."""
    return (device.memory_stats() or {}).get(key, 0)


def token_match(a, b) -> float:
    same = sum(x == y for rid in a for x, y in zip(a[rid], b[rid]))
    return same / max(1, sum(len(v) for v in a.values()))


def release(eng):
    """Free an engine's device cache now (its jitted closures keep the
    engine itself alive until a garbage-collector pass)."""
    eng.cache = None
    gc.collect()


def teacher_forcing(reqs):
    """The first SLOTS prompts, and teacher_forced_logits' arguments for
    them. Its cache is only as long as the longest prompt plus
    PARITY_STEPS, rounded up to whole pages: at MAX_LEN, the
    prefilled cache and the page pool made from it (3 GB each for 8
    rows) do not fit beside the weights on one chip."""
    prompts = [r.prompt for r in reqs[:SLOTS]]
    rows = max(len(p) for p in prompts) + PARITY_STEPS
    return prompts, dict(steps=PARITY_STEPS, max_len=-(-rows // PAGE) * PAGE,
                         page_size=PAGE)


def check_finite(tag, what, logits):
    import numpy as np
    bad = sum(int(np.sum(~np.isfinite(x))) for x in logits)
    check(bad == 0, f"[{tag}] {bad} non-finite {what} logits")
    log(f"[{tag}] teacher-forced {what} logits finite over "
        f"{sum(x.shape[0] for x in logits)} positions")


def parity(tag, ref, test):
    """Teacher-forced logits of the runtime under test must agree with
    the reference's within PARITY_TOL."""
    from repro.serve.parity import compare_logits
    rep = compare_logits(ref, test, tol=PARITY_TOL)
    log(f"[{tag}] teacher-forced parity: max logit deviation "
        f"{rep.max_logit_dev} (tol {rep.tol}), token match "
        f"{rep.token_match_frac} over {rep.n_tokens} positions")
    check(rep.within_tol, f"[{tag}] max logit deviation "
          f"{rep.max_logit_dev} > {rep.tol}")


def one_chip():
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.kernels.dispatch import KernelPolicy
    from repro.launch.serve import (init_serving_params, serve_dtype,
                                    serving_runtime)
    from repro.serve.parity import teacher_forced_logits

    clock = CompileClock()
    cfg = get_arch(ARCH)
    dtype = serve_dtype()
    check(dtype == "bfloat16", f"serving dtype on the chip is {dtype}")
    params = init_serving_params(cfg, SEED, dtype)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"[b] {ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads, vocab {cfg.vocab_size}; {n_params} "
        f"parameters in {dtype}")
    reqs = make_requests(cfg)

    rt_xla = serving_runtime(dtype, kernels=KernelPolicy.xla())
    eng, out_xla = serve("b", params, cfg, rt_xla, reqs, clock)
    release(eng)

    rt_pl = serving_runtime(dtype, kernels=KernelPolicy(
        **{op: "pallas" for op in PALLAS_OPS}))
    eng, out_pl = serve("c", params, cfg, rt_pl, reqs, clock)
    hlo = eng._step.lower(eng.params, eng.cache,
                          jnp.asarray(eng.last_tokens)).as_text()
    check("tpu_custom_call" in hlo,
          "[c] the Pallas-policy decode step holds no tpu_custom_call")
    log(f"[c] decode step lowers to Mosaic: "
        f"{hlo.count('tpu_custom_call')} tpu_custom_call sites; served "
        f"token match vs (b) {token_match(out_xla, out_pl)}")
    release(eng)
    prompts, tf = teacher_forcing(reqs)
    ref, fed = teacher_forced_logits(params, cfg, rt_xla, prompts, **tf)
    check_finite("b", "XLA", ref)
    test, _ = teacher_forced_logits(params, cfg, rt_pl, prompts,
                                    forced=fed, **tf)
    check_finite("c", "Pallas", test)
    parity("c", ref, test)


def four_chips():
    import jax
    from repro.configs import get_arch
    from repro.dist.sharding import reset_spec_drops, spec_drops
    from repro.kernels.dispatch import KernelPolicy
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import (init_serving_params, serve_dtype,
                                    serving_runtime)
    from repro.serve.parity import teacher_forced_logits

    clock = CompileClock()
    cfg = get_arch(ARCH)
    dtype = serve_dtype()
    # XLA on both engines: the phase compares sharded with one device
    rt = serving_runtime(dtype, kernels=KernelPolicy.xla())
    reqs = make_requests(cfg)
    prompts, tf = teacher_forcing(reqs)

    params = init_serving_params(cfg, SEED, dtype)
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    eng, out_one = serve("4/one-device", params, cfg, rt, reqs, clock)
    release(eng)
    ref, fed = teacher_forced_logits(params, cfg, rt, prompts, **tf)
    check_finite("4/one-device", "one-device", ref)

    reset_spec_drops()
    mesh = make_mesh((1, 4), ("data", "model"))
    eng, out_sh = serve("4/sharded", params, cfg, rt, reqs, clock,
                        mesh=mesh)
    del params                  # only the engine's sharded copy remains
    gc.collect()
    per_dev = {d: 0 for d in jax.devices()}
    for leaf in jax.tree.leaves(eng.params):
        for sh in leaf.addressable_shards:
            per_dev[sh.device] += sh.data.nbytes
    for d, nb in per_dev.items():
        used = mem_stat(d, "bytes_in_use")
        log(f"[4] device {d.id}: parameters {nb} B "
            f"({nb / param_bytes:.4f} of {param_bytes}), bytes_in_use "
            f"{used}")
        check(nb <= MAX_PARAM_SHARE * param_bytes,
              f"[4] device {d.id} holds {nb / param_bytes:.2f} of the "
              f"parameter bytes")
    drops = Counter((d.path or "activation", d.reason) for d in spec_drops())
    log("[4] sharding spec drops: " + "; ".join(
        f"{path} {why} x{n}" for (path, why), n in sorted(drops.items())))
    log(f"[4] served token match sharded vs one device "
        f"{token_match(out_one, out_sh)}")
    test, _ = teacher_forced_logits(eng.params, cfg, rt, prompts,
                                    forced=fed, ctx=eng._ctx, **tf)
    check_finite("4/sharded", "sharded", test)
    parity("4", ref, test)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-serving phase on a "
                         "(data=1, model=4) mesh of four chips")
    args = ap.parse_args()
    want = 4 if args.four_chips else 1
    try:
        device = device_phase(want)
        from repro.launch.compile_cache import enable_compile_cache
        log(f"compile cache: {enable_compile_cache()}")
        (four_chips if args.four_chips else one_chip)()
    except SmokeFailure as e:
        print(f"chip smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
