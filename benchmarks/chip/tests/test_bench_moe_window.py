"""The window-and-experts configuration on the CPU: its reference's
weights are the program's, its file is the catalog's config cut where
``reduced`` says, its cost arithmetic by hand, its traffic fits, a tiny
cell of it is correct until broken, and the expert readers on a recorded
trace of the cell."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
sys.path.insert(0, str(CHIP))

import devtrace  # noqa: E402
import enginetrace  # noqa: E402
import faults  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402

MOE = run.architecture({"reference": "moe_window"})
FILE = json.loads((CHIP / "configs" / "mellum2-12b-a2.5b.json").read_text())
TINY = json.loads((HERE / "data" / "tiny_mellum.json").read_text())
TINY_ARCH = "tiny-mellum"


@pytest.fixture
def tiny_arch(monkeypatch, tmp_path):
    """The registry's mellum2-12b-a2.5b at the tiny file's widths, under
    the name the file gives; JAX settings a run changes are restored."""
    import jax
    from repro import configs
    m = TINY["model"]
    base = configs.get_arch("mellum2-12b-a2.5b")
    cfg = base.replace(
        name=TINY_ARCH, n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_head=m["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        sliding_window=m["sliding_window"],
        moe=configs.MoEConfig(n_experts=m["router_experts"],
                              experts_per_token=m["num_experts_per_tok"],
                              d_expert=m["moe_intermediate_size"]))
    real = configs.get_arch
    monkeypatch.setattr(configs, "get_arch",
                        lambda n: cfg if n == TINY_ARCH else real(n))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    saved = jax.config.jax_persistent_cache_min_compile_time_secs
    yield cfg
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved)


# ----------------------------------------------------------- the program
def test_reference_weights_are_the_programs(tiny_arch):
    import jax
    from repro.configs import get_arch
    from repro.launch.serve import init_serving_params
    cfg = MOE.program_config(get_arch(TINY_ARCH), TINY["model"])
    assert cfg.moe.n_held == 8
    seed = run.param_seed(2 ** 33 + 5)
    ours = MOE.make_weights(TINY["model"], seed, "float32")
    flat = jax.tree_util.tree_flatten_with_path(
        init_serving_params(cfg, seed, "float32"))[0]
    theirs = {jax.tree_util.keystr(p): v for p, v in flat}
    assert set(ours) == set(theirs)
    for path, v in ours.items():
        assert np.array_equal(np.asarray(v), np.asarray(theirs[path])), path


def test_program_config_sets_the_held_experts_and_refuses_the_rest():
    from repro.configs import get_arch
    cfg = MOE.program_config(get_arch("mellum2-12b-a2.5b"), FILE["model"])
    assert (cfg.moe.n_experts, cfg.moe.n_held, cfg.moe.held) == (64, 16, 16)
    for key, value in (("sliding_window", 512), ("router_experts", 32),
                       ("num_experts_per_tok", 4)):
        with pytest.raises(SystemExit, match="differs"):
            MOE.program_config(get_arch("mellum2-12b-a2.5b"),
                               dict(FILE["model"], **{key: value}))


def test_file_holds_the_catalog_config_cut_where_reduced_says():
    """Every key of the published config.json at the file's top level,
    the same in the ``model`` the harness reads; only ``num_experts``
    differs from the source, and the router keeps the source's count."""
    catalog = {k: v for k, v in FILE.items() if k in FILE["model"]}
    assert len(catalog) == 23
    assert all(FILE["model"][k] == v for k, v in catalog.items())
    assert FILE["reduced"] == ["num_experts"]
    assert FILE["model"]["router_experts"] \
        == FILE["source_values"]["num_experts"] == 64
    assert FILE["num_experts"] == 16
    assert MOE.layer_kinds(FILE["model"]) == (("window",) * 3 + ("full",)) * 7


# ----------------------------------------------------------------- costs
# tiny: 8 layers (6 window, 2 full), d 64, 4 heads and 2 kv heads of 16,
# vocab 256, 8 of 16 experts of width 32 held, 4 per token, window 32
ATTN = 64 * 64 * 2 + 64 * 32 * 2          # 12,288
EXPERT = 3 * 64 * 32                      # 6,144
LAYER_FLOPS = 2 * (ATTN + 64 * 16 + 2 * EXPERT)   # 2 held rows a token


def test_params_and_rows():
    m = MOE.ModelCosts(TINY["model"])
    assert (m.attn_params, m.expert_params) == (ATTN, EXPERT)
    assert m.held_rows_per_token == 2.0
    # layers (attention, router, 8 experts, 2 norms) + table + head + norm
    assert m.params == 8 * (ATTN + 64 * 16 + 8 * EXPERT + 128) \
        + 2 * 64 * 256 + 64 == 533_568
    assert m.kv_bytes_per_token == 2 * 8 * 2 * 16 * 2 == 1024


def test_flops_cap_window_layers_at_the_window():
    m = MOE.ModelCosts(TINY["model"])
    # context 10: all 8 layers see 10 rows; context 100: the 6 window
    # layers see 32, the 2 full layers 100
    # layers see 32, the 2 full layers 100; a row costs 4 * 4 heads * 16
    assert m.token_flops(10) == 8 * LAYER_FLOPS + 256 * 80 == 430_080
    assert m.token_flops(100) == 8 * LAYER_FLOPS + 256 * 392 == 509_952
    # 40 prompt tokens: pairs 2 * 820 full + 6 * (528 + 8 * 32) window
    assert m.prefill_flops(40) == 40 * 8 * LAYER_FLOPS + 256 * 6_344 \
        + 2 * 64 * 256 == 18_040_832
    assert m.decode_flops([10, 100]) == 430_080 + 509_952 + 2 * 32_768


def test_decode_bytes_count_the_experts_two_tokens_hit():
    m = MOE.ModelCosts(TINY["model"])
    # 2 tokens, 4 of 16 each: 8 * (1 - (3/4)^2) = 3.5 held experts a layer
    assert m.experts_read(2) == pytest.approx(3.5)
    weights = 8 * (ATTN + 64 * 16 + 3.5 * EXPERT + 128) + 64 \
        + 64 * 256 + 2 * 64
    kv = ((80 - 8) + (392 - 8)) * 128 + 2 * 8 * 128
    assert m.decode_bytes([10, 100]) == pytest.approx(2 * weights + kv)
    assert m.decode_bytes([10, 100]) == pytest.approx(652_672)
    peak = {"bf16_flops": 1e9, "hbm_bytes_per_s": 1e6}
    assert m.decode_seconds_bound([10, 100], peak) == pytest.approx(0.652672)


# --------------------------------------------------------------- traffic
def test_agentcode_replay_fits_and_passes_the_window():
    tr = json.loads((CHIP / "traffic" / "agentcode.json").read_text())
    serve = FILE["serve"]
    rows = tr["replay"]
    assert len(rows) == 48 and tr["loop"] == "closed"
    assert all(p + o <= serve["max_len"] for p, o in rows)
    assert all(FILE["sliding_window"] < p <= max(serve["buckets"])
               for p, _ in rows)
    assert all(256 <= o <= 1024 for _, o in rows)
    items = traffic.closed_loop(tr, 2 ** 33 + 3, FILE["vocab_size"],
                                run.CLOSED_ITEMS)
    traffic.check_fits(items, serve["max_len"])
    # the first 32 (one a slot, primed in set-up) finish at staggered times
    first = sorted(o for _, o in rows[:serve["n_slots"]])
    assert first[0] < 300 and first[-1] > 1000


def test_codegen_traffic_fits_starcoder2():
    tr = json.loads((CHIP / "traffic" / "codegen.json").read_text())
    cfg = json.loads((CHIP / "configs" / "starcoder2-3b.json").read_text())
    items = traffic.open_loop(tr, 51, 2 ** 33 + 3, 49152)
    traffic.check_fits(items, cfg["serve"]["max_len"])
    lens = [len(it.prompt) for it in items]
    outs = [it.out_len for it in items]
    assert 64 <= min(lens) and max(lens) <= 512
    assert 64 <= min(outs) and max(outs) <= 1024
    assert len(items) == int(tr["rate_rps"] * 51)


# ------------------------------------------------------------ a tiny cell
@pytest.mark.parametrize("fault", (None, "half_batch", "token_altered"))
def test_tiny_cell_is_correct_until_broken(tiny_arch, fault):
    tr = json.loads((HERE / "data" / "tiny_agentcode.json").read_text())
    metrics = [dict(name=n, unit="u", _kind="end_to_end")
               for n in ("output_tok_per_s", "setup_s")]
    cell = run.Cell(name="tiny.agentcode", chips=1, config=TINY, traffic=tr,
                    metrics=metrics)
    with faults.planted(fault):
        out = run.run(cell, seed=2 ** 33 + 11, seconds=1.5, trace=False,
                      device_check=False)
    assert out["failed"] == 0 and out["metrics"]["output_tok_per_s"]
    assert out["correct"] is (fault is None), out["checks"]


# --------------------------------------------------------------- readers
def _record(trace=None):
    cell = run.Cell(name="x", chips=1, config={}, traffic={}, metrics=[])
    rec = run.RunRecord(cell=cell, seed=0, seconds=1.0, loop="closed",
                        t_open=0.0, t_close=1.0)
    rec.model = MOE.ModelCosts(FILE["model"])
    rec.peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    rec.trace = trace
    return rec


def test_expert_readers_read_nothing_without_scopes_or_counters():
    """The harness as it is: no scopes in its trace summary, no counters
    in its record. Neither reader raises; each returns None."""
    summary = {"window_s": 1.0, "busy_s": 0.9,
               "steps": [{"decode_s": 0.04}, {"decode_s": 0.05}]}
    for trace in (None, summary):
        rec = _record(trace)
        for name in ("expert_roofline", "expert_step_share"):
            assert run.metric_reader(name)(rec) is None
    rec = _record(dict(summary, scopes={"moe_gemm": 0.02}))
    assert run.metric_reader("expert_roofline")(rec) is None   # no counts


def test_expert_readers_by_hand():
    """Two decode steps of 40 and 50 ms; 20 ms under ``moe_gemm`` and 7
    under ``moe_route``; 28 layers x 2 steps x 64 rows on the 16 held
    experts, all 16 hit in every layer and step."""
    rec = _record({"window_s": 1.0, "busy_s": 0.09,
                   "steps": [{"decode_s": 0.04}, {"decode_s": 0.05}],
                   "scopes": {"moe_gemm": 0.02, "moe_route": 0.007,
                              "paged_decode_attention": 0.03}})
    assert run.metric_reader("expert_step_share")(rec) \
        == pytest.approx(100 * 0.027 / 0.09)
    rec.expert_rows, rec.experts_hit = 28 * 2 * 64, 28 * 2 * 16
    expert = 3 * 2304 * 896
    bytes_ = 2 * (28 * 2 * 16 * expert + 2 * 2304 * 28 * 2 * 64)
    flops = 2 * expert * 28 * 2 * 64
    assert bytes_ / 819e9 > flops / 197e12          # the weights bound it
    assert run.metric_reader("expert_roofline")(rec) \
        == pytest.approx(100 * bytes_ / 819e9 / 0.02)


def _recorded():
    """``data/trace_agentcode_engine.json``: three engine steps of the
    ``.agentcode`` cell on a TPU v5e (32 slots decoding, none admitted),
    ops of 20 us and longer, the decode program's op names and the
    engine's counters over the steps; the record and the scopes the
    readers would get from a harness that keeps them."""
    import expertscopes
    d = json.loads((HERE / "data" / "trace_agentcode_engine.json")
                   .read_text())
    r = d["record"]
    rec = _record()
    rec.t_open, rec.t_close = r["t_open"], r["t_close"]
    rec.seconds = r["t_close"] - r["t_open"]
    rec.peak = r["peak"]
    rec.steps = [run.StepRecord(s["t0"], s["t1"], s["admitted"],
                                {int(k): v for k, v in s["served"].items()},
                                s["contexts"]) for s in r["steps"]]
    rec.trace = devtrace.summarize(d, rec)
    w0, w1 = devtrace._window(d["host"])
    dev = next(iter(d["devices"].values()))
    rec.trace["scopes"] = enginetrace.scopes(
        dev, w0, w1, expertscopes.names(d["step_names"]),
        expertscopes.known())
    return rec, r["counters"]


def test_recorded_trace_reduces_to_the_expert_metrics():
    rec, counters = _recorded()
    assert len(rec.trace["steps"]) == 3
    assert all(len(s.contexts) == 32 for s in rec.steps)
    # every layer of every step routes its 32 x 8 rows; about a quarter
    # land on the 16 held experts, most of which some row hits
    assert 0.15 < counters["expert_rows"] / (3 * 28 * 256) < 0.35
    assert 0.6 < counters["experts_hit"] / (3 * 28 * 16) <= 1.0
    assert counters["ring_alloc_token_steps"] == 3 * 32 * 1024
    share = run.metric_reader("expert_step_share")(rec)
    decode = sum(s["decode_s"] for s in rec.trace["steps"])
    assert share == pytest.approx(100 * (
        rec.trace["scopes"]["moe_gemm"]
        + rec.trace["scopes"].get("moe_route", 0.0)) / decode)
    assert 20 < share < 100
    assert run.metric_reader("expert_roofline")(rec) is None   # no counts
    rec.expert_rows = counters["expert_rows"]
    rec.experts_hit = counters["experts_hit"]
    roof = run.metric_reader("expert_roofline")(rec)
    m = rec.model
    bound = m.b * (counters["experts_hit"] * m.expert_params
                   + 2 * m.d * counters["expert_rows"]) / 819e9
    assert roof == pytest.approx(100 * bound
                                 / rec.trace["scopes"]["moe_gemm"])
    assert 0 < roof < 100
