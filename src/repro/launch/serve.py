"""Serving launcher: ``python -m repro.launch.serve --arch <id> ...``

Scheduled continuous batching over the selected architecture (reduced
config on CPU with ``--smoke``): bucketed/chunked prefill, seeded
sampling (greedy / temperature / top-k), cache-budget admission, and —
with ``--mesh`` — a sharded slot batch over a device mesh via the
``repro.dist`` decode recipe. Prints tok/s, per-step latency
percentiles, slot occupancy, prefill compile count, and any rejected
requests.

On a TPU the engine serves in bfloat16 — runtime, stored parameters,
and the preflight/deploy models alike; on the CPU (the ``--smoke`` test
path) in float32. :func:`build_engine` is the one engine constructor
``main`` and ``chip_smoke.py`` share.
"""
from __future__ import annotations

import argparse
import logging
import time
from typing import Optional, Sequence

import numpy as np

import jax

from repro.configs import get_arch, smoke_config
from repro.configs.base import ModelConfig
from repro.core.workload.registry import resolve_arch
from repro.kernels.dispatch import KernelPolicy
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.models.model import ModelRuntime
from repro.serve import (PagedServeEngine, Request, Sampler, Scheduler,
                         ServeEngine, ShardedPagedServeEngine,
                         ShardedServeEngine)


def serve_dtype() -> str:
    """Compute and parameter dtype: bf16 on the chip, f32 on the CPU."""
    return "bfloat16" if jax.default_backend() == "tpu" else "float32"


def serving_kernels(sharded: bool = False) -> KernelPolicy:
    """The serving default: on a TPU, with the page pool on one device,
    paged decode attention runs the live-page Pallas kernel and an
    expert layer its held experts' rows as grouped GEMMs
    (``moe_gemm='ragged'``); every other op stays XLA, as does
    everything on the CPU and under a mesh (a ``pallas_call`` has no
    partitioning rule)."""
    if jax.default_backend() == "tpu" and not sharded:
        return KernelPolicy(paged_decode_attention="pallas",
                            moe_gemm="ragged")
    return KernelPolicy.xla()


def serving_runtime(dtype: str, kv_dtype: Optional[str] = None,
                    kernels: Optional[KernelPolicy] = None,
                    mesh=None) -> ModelRuntime:
    """The launcher's runtime; ``kernels`` given wins over
    :func:`serving_kernels`, which sees whether a ``mesh`` shards the
    engine."""
    if kernels is None:
        kernels = serving_kernels(sharded=mesh is not None)
    return ModelRuntime(dtype=dtype, remat="none", attn_chunk=128,
                        moe_dropless=True, kv_dtype=kv_dtype,
                        kernels=kernels)


def init_serving_params(cfg: ModelConfig, seed: int, dtype: str):
    """Seeded random parameters held in ``dtype`` from the start: init
    and cast run as one jitted program, so float32 masters never sit on
    the device beside their ``dtype`` copies."""
    def init(key):
        return jax.tree.map(lambda a: a.astype(dtype), init_params(key, cfg))
    return jax.jit(init)(jax.random.PRNGKey(seed))


def build_engine(params, cfg: ModelConfig, rt: ModelRuntime, *,
                 n_slots: int, max_len: int,
                 buckets: Optional[Sequence[int]] = None,
                 admit_width: int = 1, sampler: Optional[Sampler] = None,
                 overflow: str = "reject", eos_id: Optional[int] = None,
                 page_size: int = 0, page_budget: Optional[int] = None,
                 prefix_cache: bool = True, mesh=None) -> ServeEngine:
    """The serving engine the launcher runs: paged when ``page_size``
    > 0, sharded over ``mesh`` when one is given."""
    sched = Scheduler(cfg=cfg, max_len=max_len, buckets=buckets,
                      admit_width=admit_width)
    kw = dict(n_slots=n_slots, max_len=max_len, sampler=sampler,
              scheduler=sched, overflow=overflow, eos_id=eos_id)
    if page_size > 0:
        kw.update(page_size=page_size, page_budget=page_budget,
                  prefix_cache=prefix_cache)
    if mesh is not None:
        eng_cls = ShardedPagedServeEngine if page_size > 0 \
            else ShardedServeEngine
        return eng_cls(params, cfg, rt, mesh, **kw)
    eng_cls = PagedServeEngine if page_size > 0 else ServeEngine
    return eng_cls(params, cfg, rt, **kw)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--buckets", default=None,
                    help="comma-separated prefill bucket lengths "
                         "(default: powers of two up to max-len; "
                         "'exact' disables bucketing)")
    ap.add_argument("--admit-width", type=int, default=1,
                    help="fixed batch width of every prefill call")
    ap.add_argument("--sampler", choices=("greedy", "temperature"),
                    default="greedy")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--eos", type=int, default=None,
                    help="token id terminating a request early")
    ap.add_argument("--overflow", choices=("reject", "truncate", "error"),
                    default="reject",
                    help="policy for prompt+max-new > max-len requests")
    ap.add_argument("--page-size", type=int, default=0,
                    help="KV page size in tokens; > 0 selects the paged "
                         "engine (pooled pages + page tables instead of "
                         "per-slot contiguous caches)")
    ap.add_argument("--page-budget", type=int, default=None,
                    help="total pages in the pool incl. the null page "
                         "(default: slots * ceil(W/page_size) + 1 — the "
                         "fixed engine's KV HBM)")
    ap.add_argument("--kv-dtype", choices=("bfloat16", "int8"),
                    default=None,
                    help="KV-cache storage precision (default: the "
                         "runtime compute dtype). 'int8' quantizes "
                         "per-(token, head) with bf16 scale side-bands; "
                         "the paged engine re-denominates the same byte "
                         "budget into ~2x pages")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="share prompt-prefix pages across requests "
                         "(paged engine only)")
    ap.add_argument("--mesh", default=None,
                    help="DxM device mesh, e.g. 2x4 -> (data, model); "
                         "shards the engine via the decode recipe")
    ap.add_argument("--preflight", action="store_true",
                    help="gate the config through the closed-form HBM "
                         "capacity model before allocating anything; "
                         "reject oversized slots/max-len/page budgets "
                         "(rule capacity-hbm-overflow)")
    ap.add_argument("--hbm-gb", type=float, default=None,
                    help="per-device HBM budget in GiB for --preflight "
                         "(default: TPU v5e)")
    ap.add_argument("--scenario", default=None,
                    help="named traffic scenario (repro.serve.scenarios) "
                         "to sample requests from; with --preflight also "
                         "runs the deploy_lint feasibility rules against "
                         "it (scaled into --max-len if needed)")
    ap.add_argument("--strict", action="store_true",
                    help="refuse to launch on deploy-admission-deadlock "
                         "(and any other error-severity deploy finding)")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO)
    enable_compile_cache()
    dtype = serve_dtype()
    cfg = get_arch(resolve_arch(args.arch))
    if args.smoke:
        cfg = smoke_config(cfg)
    if cfg.is_encoder_only:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode step")

    if args.buckets == "exact":
        buckets = ()
    elif args.buckets:
        buckets = tuple(int(b) for b in args.buckets.split(","))
    else:
        buckets = None

    scenario = None
    if args.scenario:
        # deploy_preflight is jax-free closed-form math: always worth
        # running when a scenario names the traffic we are about to serve
        from repro.analysis.deploy_lint import (DeploymentSpec,
                                                deploy_preflight)
        from repro.serve.scenarios import get_scenario
        mesh_sizes = None
        if args.mesh:
            d, m = (int(x) for x in args.mesh.split("x"))
            mesh_sizes = {"data": d, "model": m}
        scenario = get_scenario(args.scenario).scaled(args.max_len)
        dep = DeploymentSpec(
            n_slots=args.slots, max_len=args.max_len, buckets=buckets,
            admit_width=args.admit_width, page_size=args.page_size,
            page_budget=args.page_budget, dtype=dtype, param_dtype=dtype,
            kv_dtypes=(args.kv_dtype,) if args.kv_dtype else (),
            mesh=mesh_sizes, hbm_gb=args.hbm_gb)
        drep = deploy_preflight(cfg, scenario, deployment=dep)
        if args.preflight:
            print(f"deploy[{scenario.name}]: rho={drep.rho:.3f} "
                  f"(peak {drep.rho_peak:.3f}) at batch={drep.best_batch}; "
                  f"lower bounds tok p50/p99 {drep.tok_p50_lb_ms:.3f}/"
                  f"{drep.tok_p99_lb_ms:.3f} ms, ttft "
                  f"{drep.ttft_lb_ms:.1f} ms; compiles {drep.compiles} "
                  f"(bound {drep.compile_bound or 'unbounded'}); cache "
                  f"{drep.cache_tokens} tokens")
            for f in drep.findings:
                print(f"  [{f.severity}] {f.rule_id}: {f.message}")
        errors = [f for f in drep.findings if f.severity == "error"]
        if args.strict and errors:
            raise SystemExit(
                f"[{errors[0].rule_id}] scenario {scenario.name!r} is "
                f"statically infeasible on this config: "
                f"{errors[0].message}")

    if args.preflight:
        # capacity() is pure shape math — runs before any device buffer
        # exists, so an oversized config costs nothing to reject
        from repro.analysis.capacity import serve_preflight
        mesh_sizes = None
        if args.mesh:
            d, m = (int(x) for x in args.mesh.split("x"))
            mesh_sizes = {"data": d, "model": m}
        cap = serve_preflight(
            cfg, n_slots=args.slots, max_len=args.max_len,
            page_size=args.page_size or None,
            page_budget=args.page_budget, mesh=mesh_sizes,
            hbm_gb=args.hbm_gb, kv_dtype=args.kv_dtype,
            dtype=dtype)   # matches the runtime constructed below
        print(f"preflight: predicted peak "
              f"{cap.peak_bytes / 2**30:.3f} GiB / "
              f"{cap.hbm_bytes / 2**30:.1f} GiB per device "
              f"(params {cap.params_bytes / 2**30:.3f} GiB, cache "
              f"{cap.cache_bytes / 2**30:.3f} GiB, recipe {cap.recipe}, "
              f"utilization {cap.utilization:.2f})")
        if not cap.fits:
            raise SystemExit(
                f"[capacity-hbm-overflow] {args.slots} slots x "
                f"{args.max_len} tokens predicts "
                f"{cap.peak_bytes / 2**30:.2f} GiB peak per device, over "
                f"the {cap.hbm_bytes / 2**30:.1f} GiB budget — shrink "
                f"--slots/--max-len, page the cache, or shard wider")

    params = init_serving_params(cfg, args.seed, dtype)
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_mesh
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))
    rt = serving_runtime(dtype, kv_dtype=args.kv_dtype, mesh=mesh)
    eng = build_engine(
        params, cfg, rt, n_slots=args.slots, max_len=args.max_len,
        buckets=buckets, admit_width=args.admit_width,
        sampler=Sampler(kind=args.sampler, temperature=args.temperature,
                        top_k=args.top_k, seed=args.seed),
        overflow=args.overflow, eos_id=args.eos, page_size=args.page_size,
        page_budget=args.page_budget, prefix_cache=args.prefix_cache,
        mesh=mesh)

    rng = np.random.default_rng(args.seed)
    if scenario is not None:
        # request shapes come from the scenario spec, so the measured
        # run replays exactly what deploy_preflight bounded
        for i, (_, plen, olen) in enumerate(
                scenario.sample_requests(args.requests, seed=args.seed)):
            prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
            eng.submit(Request(rid=i, prompt=prompt, max_new_tokens=olen))
    else:
        for i in range(args.requests):
            plen = int(rng.integers(4, max(5, min(32, args.max_len // 2))))
            prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
            eng.submit(Request(rid=i, prompt=prompt,
                               max_new_tokens=args.max_new))

    t0 = time.time()
    step_s = []
    while eng.queue or any(s is not None for s in eng.slots):
        t1 = time.time()
        eng.step()
        step_s.append(time.time() - t1)
    dt = time.time() - t0
    done = eng.finished

    toks = sum(len(r.out_tokens) for r in done)
    st = eng.stats
    p50, p99 = (np.percentile(step_s, (50, 99)) * 1e3
                if step_s else (float("nan"),) * 2)
    print(f"served {len(done)}/{args.requests} requests, {toks} tokens "
          f"in {dt:.1f}s ({toks / dt:.1f} tok/s on "
          f"{jax.device_count()} device(s))")
    print(f"  step latency p50/p99 {p50:.1f}/{p99:.1f} ms; slot "
          f"occupancy {st.occupancy(args.slots):.2f}; prefill compiles "
          f"{st.prefill_compiles} (bound "
          f"{eng.scheduler.max_prefill_compiles() or 'unbounded'}); "
          f"forced prompt tokens {st.forced_tokens}")
    print(f"  kv cache {eng.kv_cache_bytes() / 2**20:.1f} MiB, "
          f"utilization {st.kv_utilization:.2f}, max in-flight "
          f"{st.max_active}")
    if args.page_size > 0:
        print(f"  pages: size={args.page_size} pool={eng.pages.n_pages} "
              f"free={eng.pages.free_pages} prefix hit_rate="
              f"{eng.prefix_hit_rate:.2f} hits={st.prefix_hits} "
              f"hit_tokens={st.prefix_hit_tokens} "
              f"evictions={eng.pages.evictions}")
    if eng.rejected:
        print(f"  rejected {len(eng.rejected)}: "
              f"{[(r.rid, r.finish_reason) for r in eng.rejected]}")
    for r in done[:4]:
        print(f"  rid={r.rid} finish={r.finish_reason} "
              f"out={r.out_tokens}")


if __name__ == "__main__":
    main()
