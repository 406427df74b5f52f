"""Pipeline-parallel dry-run — the paper's paradigm-1 *spatial* mode.

Mesh (stage=4, data=8, model=8) = 256 chips: each stage group holds a
contiguous quarter of the layer stack (its own 'dedicated pipeline
stage'), microbatches stream through `collective_permute`, and the
whole schedule (fwd + pipelined bwd via jax.grad) lowers and compiles.

    PYTHONPATH=src python -m repro.launch.dryrun_pp --arch chatglm3-6b

Importing this module has no side effects; the forced host-device
count is set on the ``__main__`` path only.
"""
import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.artifacts import pp_dir
from repro.configs import get_arch, get_shape
from repro.core.roofline import collective_bytes_from_hlo
from repro.dist.pipeline import stage_split
from repro.launch.lowering import cost_analysis_dict
from repro.launch.mesh import make_mesh, use_mesh
from repro.launch.presets import force_host_devices
from repro.models import abstract_params
from repro.models.layers import cross_entropy
from repro.models.model import ModelRuntime, attn_block, norm


def lower_pp(arch: str = "chatglm3-6b", n_stages: int = 4,
             n_micro: int = 8, mb: int = 32, seq: int = 4096):
    cfg = get_arch(arch)
    assert cfg.n_layers % n_stages == 0
    mesh = make_mesh((n_stages, 8, 8), ("stage", "data", "model"))
    rt = ModelRuntime(dtype="bfloat16", remat="full", attn_chunk=512)
    positions = jnp.arange(seq, dtype=jnp.int32)[None, :]

    def stage_fn(local_blocks, x):
        def body(h, lp):
            h2, _, _ = attn_block(lp, h, positions, cfg, rt)
            return h2, None
        x, _ = jax.lax.scan(body, x, local_blocks)
        return x

    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def pp_inner(staged_blocks, x_micro):
        local = jax.tree.map(lambda a: a[0], staged_blocks)
        stage_idx = jax.lax.axis_index("stage")
        recv = jnp.zeros(x_micro.shape[1:], x_micro.dtype)
        out_buf = jnp.zeros_like(x_micro)

        def body(carry, t):
            recv, out_buf = carry
            src = x_micro[jnp.minimum(t, n_micro - 1)]
            inp = jnp.where(stage_idx == 0, src, recv)
            out = stage_fn(local, inp)
            mb_idx = t - (n_stages - 1)
            valid = (stage_idx == n_stages - 1) & (mb_idx >= 0)
            out_buf = jax.lax.cond(
                valid,
                lambda b: jax.lax.dynamic_update_index_in_dim(
                    b, out, jnp.maximum(mb_idx, 0), 0),
                lambda b: b, out_buf)
            recv = jax.lax.ppermute(out, "stage", perm)
            return (recv, out_buf), None

        (recv, out_buf), _ = jax.lax.scan(
            body, (recv, out_buf), jnp.arange(n_micro + n_stages - 1))
        mask = (stage_idx == n_stages - 1).astype(out_buf.dtype)
        return jax.lax.psum(out_buf * mask, "stage")

    pp = jax.shard_map(pp_inner, mesh=mesh,
                       in_specs=(P("stage"), P(None, "data")),
                       out_specs=P(None, "data"), check_vma=False)

    def loss_fn(params, tokens, labels):
        x = params["embed"].astype(rt.dtype)[tokens]      # (M, mb, S, d)
        staged = stage_split(params["blocks"], n_stages)
        x = pp(staged, x)
        x = norm(x, params["final_norm"], cfg.norm)
        logits = x @ params["lm_head"].astype(x.dtype)
        return cross_entropy(logits, labels)

    def train_grads(params, tokens, labels):
        return jax.value_and_grad(loss_fn)(params, tokens, labels)

    # abstract inputs
    ap = abstract_params(cfg)

    def shard_param(path_leaf):
        return NamedSharding(mesh, P())

    aps = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, P())), ap)
    # stage-shard the block stack leaves on the layer dim
    aps["blocks"] = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype,
            sharding=NamedSharding(mesh, P("stage"))), ap["blocks"])
    tok = jax.ShapeDtypeStruct(
        (n_micro, mb, seq), jnp.int32,
        sharding=NamedSharding(mesh, P(None, "data")))
    lab = jax.ShapeDtypeStruct(
        (n_micro, mb, seq), jnp.int32,
        sharding=NamedSharding(mesh, P(None, "data")))

    t0 = time.time()
    with use_mesh(mesh):
        lowered = jax.jit(train_grads).lower(aps, tok, lab)
        compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    cost = cost_analysis_dict(compiled)
    coll = collective_bytes_from_hlo(compiled.as_text())
    art = {
        "arch": arch, "mode": "pipeline-parallel",
        "mesh": f"(stage={n_stages}, data=8, model=8)",
        "n_micro": n_micro, "status": "OK",
        "compile_s": round(t_compile, 1),
        "memory_gb_per_chip": {
            "argument": round(mem.argument_size_in_bytes / 2**30, 2),
            "temp": round(mem.temp_size_in_bytes / 2**30, 2),
        },
        "flops_per_chip": float(cost.get("flops", 0.0)),
        "collective_permute_gb": round(
            coll["collective-permute"] / 2**30, 2),
        "collectives_total_gb": round(coll["total"] / 2**30, 2),
    }
    return art


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="chatglm3-6b")
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--micro", type=int, default=8)
    args = ap.parse_args()
    force_host_devices(args.stages * 8 * 8)
    art = lower_pp(args.arch, args.stages, args.micro)
    out = pp_dir()
    os.makedirs(out, exist_ok=True)
    path = os.path.join(out, f"{args.arch}__pp__stage{args.stages}.json")
    with open(path, "w") as f:
        json.dump(art, f, indent=1)
    print(json.dumps(art, indent=1))


if __name__ == "__main__":
    main()
