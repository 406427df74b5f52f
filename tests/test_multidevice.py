"""Multi-device behaviours that need >1 device: pipeline parallelism,
elastic checkpoint re-sharding, recipe-sharded train step. Run in a
subprocess so the forced host-device count doesn't leak into the rest
of the suite (jax locks device count at first init)."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_pipeline_parallel_matches_sequential():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.dist.pipeline import pipeline_apply, stage_split

    n_layers, n_stages, n_micro, mb, d = 8, 4, 6, 2, 16
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (n_layers, d, d)) * 0.2
    x = jax.random.normal(jax.random.fold_in(key, 1), (n_micro, mb, d))

    def layer(wi, h):
        return jnp.tanh(h @ wi)

    def stage_fn(local_w, h):
        def body(h, wi):
            return layer(wi, h), None
        h, _ = jax.lax.scan(body, h, local_w)
        return h

    # sequential reference
    def seq(h):
        def body(h, wi):
            return layer(wi, h), None
        h, _ = jax.lax.scan(body, h, w)
        return h
    want = jax.vmap(seq)(x)

    mesh = make_mesh((n_stages, 2), ("stage", "data"))
    staged = stage_split({"w": w}, n_stages)["w"]
    fn = pipeline_apply(stage_fn, mesh, n_stages)
    got = jax.jit(fn)(staged, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    # and it is differentiable (pipelined backward)
    g = jax.grad(lambda s: jnp.sum(fn(s, x) ** 2))(staged)
    assert all(bool(jnp.all(jnp.isfinite(l))) for l in jax.tree.leaves(g))
    print("PP OK")
    """)


def test_elastic_restore_reshard():
    _run("""
    import tempfile, jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.ckpt import save, restore_elastic

    tree = {"w": jnp.arange(64.0).reshape(8, 8),
            "b": jnp.arange(8.0)}
    mesh_a = make_mesh((8,), ("data",))
    put = lambda t, spec: jax.device_put(t, NamedSharding(mesh_a, spec))
    sharded = {"w": put(tree["w"], P("data")), "b": put(tree["b"], P())}
    with tempfile.TemporaryDirectory() as d:
        save(d, 1, sharded)
        # 'failure': only 4 chips survive; re-plan to a (2,2) mesh
        mesh_b = make_mesh((2, 2), ("data", "model"))
        shardings = {
            "w": NamedSharding(mesh_b, P("data", "model")),
            "b": NamedSharding(mesh_b, P("model")),
        }
        back = restore_elastic(d, 1, tree, shardings)
    np.testing.assert_array_equal(np.asarray(back["w"]),
                                  np.asarray(tree["w"]))
    assert back["w"].sharding.spec == P("data", "model")
    print("elastic OK")
    """)


def test_recipe_sharded_train_step_runs():
    _run("""
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import ARCHS, smoke_config
    from repro.dist.sharding import IS_RECIPE, param_sharding_tree
    from repro.launch.mesh import make_mesh, use_mesh
    from repro.models import init_params
    from repro.models.model import ModelRuntime, axes_tree
    from repro.train import AdamWConfig, TrainConfig
    from repro.train.loop import init_state, make_train_step

    cfg = smoke_config(ARCHS["chatglm3-6b"])
    mesh = make_mesh((2, 4), ("data", "model"))
    rt = ModelRuntime(dtype="float32", remat="none", attn_chunk=16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    shardings = param_sharding_tree(axes_tree(cfg), IS_RECIPE, mesh, params)
    params = jax.tree.map(jax.device_put, params, shardings)
    state = init_state(params)
    B, S = 4, 32
    key = jax.random.PRNGKey(1)
    bspec = NamedSharding(mesh, P("data"))
    batch = {
        "tokens": jax.device_put(
            jax.random.randint(key, (B, S), 0, cfg.vocab_size), bspec),
        "labels": jax.device_put(
            jax.random.randint(key, (B, S), 0, cfg.vocab_size), bspec),
    }
    with use_mesh(mesh):
        step = jax.jit(make_train_step(
            cfg, rt, TrainConfig(opt=AdamWConfig()), IS_RECIPE))
        state, metrics = step(state, batch)
        state, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    print("sharded train OK", float(metrics["loss"]))
    """)


def test_constrain_applies_under_use_mesh():
    """Under ``use_mesh`` + ``axis_rules`` a logical constraint reaches
    the compiled program; outside a mesh it is the identity."""
    _run("""
    import jax, jax.numpy as jnp
    from repro.dist.sharding import DECODE_RECIPE, axis_rules, constrain
    from repro.launch.mesh import make_mesh, use_mesh

    mesh = make_mesh((1, 4), ("data", "model"))
    f = jax.jit(lambda x: constrain(x * 2, ("batch", "ffn")))
    x = jnp.ones((2, 8))
    with use_mesh(mesh), axis_rules(DECODE_RECIPE):
        text = f.lower(x).as_text()
        y = f(x)
    assert "sharding_constraint" in text or "Sharding" in text, text
    assert y.sharding.spec == jax.sharding.PartitionSpec(None, "model"), \
        y.sharding
    with axis_rules(DECODE_RECIPE):
        assert "Sharding" not in f.lower(x).as_text()
    """, devices=4)


def test_sharded_serve_engine_token_parity():
    """ShardedServeEngine (decode recipe: weights TP over `model`, slot
    batch over `data`) must serve token-for-token the same output as the
    single-device engine — sharding is placement, not semantics."""
    _run("""
    import numpy as np, jax
    from repro.configs import ARCHS, smoke_config
    from repro.launch.mesh import make_mesh
    from repro.models import init_params
    from repro.models.model import ModelRuntime
    from repro.serve import Request, ServeEngine, ShardedServeEngine

    cfg = smoke_config(ARCHS["minicpm-2b"])
    rt = ModelRuntime(dtype="float32", remat="none", attn_chunk=16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = [(np.arange(3 + i) * 3 + i).astype(np.int32)
               % cfg.vocab_size for i in range(6)]

    def serve(eng):
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=6))
        return {r.rid: r.out_tokens for r in eng.run()}

    want = serve(ServeEngine(params, cfg, rt, n_slots=4, max_len=64))
    mesh = make_mesh((2, 4), ("data", "model"))
    eng = ShardedServeEngine(params, cfg, rt, mesh, n_slots=4,
                             max_len=64)
    got = serve(eng)
    assert got == want, (got, want)
    # the KV cache really is sharded: each device holds a strict
    # subset of the (layers, batch, ...) leaf
    shard = eng.cache["k"].addressable_shards[0].data
    assert shard.size < eng.cache["k"].size, (shard.shape,
                                              eng.cache["k"].shape)
    print("sharded serve OK")
    """)


def test_sharded_paged_serve_engine_token_parity():
    """ShardedPagedServeEngine (pooled kp/vp sharded along kv_heads,
    page tables replicated) serves token-for-token the same output as
    the single-device paged engine, prefix cache on."""
    _run("""
    import numpy as np, jax
    from repro.configs import ARCHS, smoke_config
    from repro.launch.mesh import make_mesh
    from repro.models import init_params
    from repro.models.model import ModelRuntime
    from repro.serve import (PagedServeEngine, Request,
                             ShardedPagedServeEngine)

    cfg = smoke_config(ARCHS["minicpm-2b"])
    rt = ModelRuntime(dtype="float32", remat="none", attn_chunk=16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    sys_prompt = rng.integers(0, cfg.vocab_size, 16)
    prompts = [np.concatenate([sys_prompt,
                               rng.integers(0, cfg.vocab_size, 3 + i)])
               .astype(np.int32) for i in range(6)]

    def serve(eng):
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=6))
        return {r.rid: r.out_tokens for r in eng.run()}

    want = serve(PagedServeEngine(params, cfg, rt, n_slots=4,
                                  max_len=64, page_size=8))
    mesh = make_mesh((2, 4), ("data", "model"))
    eng = ShardedPagedServeEngine(params, cfg, rt, mesh, n_slots=4,
                                  max_len=64, page_size=8)
    got = serve(eng)
    assert got == want, (got, want)
    assert eng.stats.prefix_hits > 0
    # the pooled KV pages really shard along kv_heads
    shard = eng.cache["kp"].addressable_shards[0].data
    assert shard.size < eng.cache["kp"].size, (shard.shape,
                                               eng.cache["kp"].shape)
    print("sharded paged serve OK")
    """)
