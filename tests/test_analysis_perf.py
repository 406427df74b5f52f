"""Performance static-analysis coverage: the spmd_lint HLO rules
(fixture HLO per rule, each firing exactly its rule id), the
capacity-model parity bar against every OK ci dry-run cell's
``memory_analysis()`` numbers (no step executes — the cells are
pre-measured JSON), the jaxpr liveness walk, the sanitize_spec drop
recorder, the sharding-propagation pass, the baseline ratchet, and the
``--preflight`` serve gate end-to-end in subprocesses.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.analysis.capacity import (PARITY_REL_TOL, _ProxyMesh,
                                     CapacityReport, capacity,
                                     capacity_from_artifact,
                                     measured_peak_bytes, serve_preflight)
from repro.analysis.findings import (Finding, Location, Report,
                                     baseline_regressions, gate_counts,
                                     load_baseline)
from repro.analysis.registry import PRESETS as ANALYSIS_PRESETS
from repro.analysis.registry import AnalysisContext
from repro.analysis import liveness, sharding_prop, spmd_lint
from repro.artifacts import dryrun_dir, list_cells
from repro.configs import get_arch, smoke_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rule_ids(findings):
    return sorted({f.rule_id for f in findings})


# ======================================================================
# spmd_lint: fixture HLO per rule
# ======================================================================
#: 64 MB all-gather whose result is the whole "parameter tree".
_GATHER_HLO = (
    "  %p0 = f32[1048576,4]{1,0} parameter(0)\n"
    "  %ag.1 = f32[16777216,1]{1,0} all-gather(f32[1048576,1]{1,0} %sh), "
    "channel_id=1, replica_groups=[1,16]<=[16], dimensions={0}\n")

_THRASH_HLO = (
    "  %rs.2 = f32[65536,8]{1,0} reduce-scatter(f32[1048576,8]{1,0} %x), "
    "channel_id=2, replica_groups={{0,1,2,3}}, dimensions={0}, "
    "to_apply=%add\n"
    "  %ag.3 = f32[1048576,8]{1,0} all-gather(f32[65536,8]{1,0} %rs.2), "
    "channel_id=3, replica_groups={{0,1,2,3}}, dimensions={0}\n")

_HOST_HLO = (
    "  %of = token[] outfeed(f32[128]{0} %data, token[] %tok), "
    "outfeed_config=\"abc\"\n")

_SEND_HLO = (
    "  %send.1 = (f32[128]{0}, u32[], token[]) send(f32[128]{0} %x, "
    "token[] %tok), channel_id=7, is_host_transfer=true\n")

_CLEAN_HLO = (
    "  %ar = f32[128,256]{1,0} all-reduce(f32[128,256]{1,0} %p0), "
    "channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add\n"
    "  %send.2 = (f32[8]{0}, u32[], token[]) send(f32[8]{0} %y, "
    "token[] %t), channel_id=9\n")     # device-device send: not a hit


def test_replicated_gather_fixture_fires_exactly_its_rule():
    param_bytes = 16777216 * 4          # the gather covers 100% of it
    found = spmd_lint.lint_lowered_hlo(
        _GATHER_HLO, label="fx", param_bytes=param_bytes, gather_frac=0.5)
    assert _rule_ids(found) == ["spmd-replicated-gather"]
    assert "100%" in found[0].message


def test_replicated_gather_inert_below_param_floor():
    # smoke-scale guard: a sub-MB parameter tree never trips the rule
    assert spmd_lint.find_replicated_gathers(
        _GATHER_HLO, param_bytes=200_000, frac=0.5) == []


def test_reshard_thrash_fixture_fires_exactly_its_rule():
    found = spmd_lint.lint_lowered_hlo(
        _THRASH_HLO, label="fx", param_bytes=0, gather_frac=0.5)
    assert _rule_ids(found) == ["spmd-reshard-thrash"]
    pair = spmd_lint.find_reshard_thrash(_THRASH_HLO)
    assert len(pair) == 1
    assert pair[0]["producer"]["name"] == "rs.2"
    assert pair[0]["consumer"]["name"] == "ag.3"


def test_host_transfer_fixtures_fire_exactly_their_rule():
    for hlo in (_HOST_HLO, _SEND_HLO):
        found = spmd_lint.lint_lowered_hlo(
            hlo, label="fx", param_bytes=0, gather_frac=0.5)
        assert _rule_ids(found) == ["spmd-host-transfer"]


def test_clean_hlo_fires_nothing():
    found = spmd_lint.lint_lowered_hlo(
        _CLEAN_HLO, label="fx", param_bytes=1 << 30, gather_frac=0.5)
    assert found == []


def test_collective_oversize_gate():
    hits = spmd_lint._parse_collective_ops(_CLEAN_HLO)
    assert hits[0]["kind"] == "all-reduce"
    assert hits[0]["bytes"] == 128 * 256 * 4
    assert spmd_lint.check_collective_oversize(100.0, 50.0, 6.0) is None
    over = spmd_lint.check_collective_oversize(400.0, 50.0, 6.0)
    assert over is not None and over["ratio"] == pytest.approx(8.0)
    # zero expectation never divides-by-zero into a false positive
    assert spmd_lint.check_collective_oversize(1e9, 0.0, 6.0) is None


def test_async_done_lines_skipped():
    hlo = ("  %ag-done.1 = f32[1048576,1]{1,0} all-gather-done("
           "f32[1048576,1]{1,0} %ag-start.1)\n")
    assert spmd_lint._parse_collective_ops(hlo) == []


def test_oversized_artifact_cell_fires_collective_rule():
    from repro.launch.presets import CI

    cells = list_cells("ci")
    if not cells:
        pytest.skip("no ci dry-run artifacts (python -m repro.launch."
                    "dryrun --preset ci)")
    with open(os.path.join(dryrun_dir("ci"), cells[0])) as f:
        art = json.load(f)
    if art.get("status") != "OK" or art.get("variant",
                                            "baseline") != "baseline":
        pytest.skip(f"first cell {cells[0]} is not an OK baseline cell")
    art = dict(art)
    art["collectives"] = dict(art["collectives"],
                              total=art["collectives"]["total"] * 1e6 + 1e12)
    found = spmd_lint.lint_artifact_cell(
        art, CI, slack=6.0, drift_tol=0.25)
    assert "spmd-collective-oversize" in _rule_ids(found)


# ======================================================================
# capacity: parity against memory_analysis() on every OK ci cell
# ======================================================================
def _ok_cells():
    cells = []
    for name in list_cells("ci"):
        with open(os.path.join(dryrun_dir("ci"), name)) as f:
            art = json.load(f)
        if art.get("status") == "OK" \
                and art.get("variant", "baseline") == "baseline":
            cells.append(art)
    return cells


def test_capacity_parity_on_every_ok_ci_cell():
    """The acceptance bar: argument bytes exact, peak within 25% of the
    measured memory_analysis() numbers — for every cell, no step run."""
    from repro.launch.presets import CI

    cells = _ok_cells()
    if not cells:
        pytest.skip("no ci dry-run artifacts (python -m repro.launch."
                    "dryrun --preset ci)")
    worst, failures = 0.0, []
    for art in cells:
        rep = capacity_from_artifact(art, CI)
        cell = f"{art['arch']}/{art['shape']}/{art['mesh']}"
        if rep.argument_bytes != art["memory"]["argument_bytes"]:
            failures.append(
                f"{cell}: args {rep.argument_bytes} != "
                f"{art['memory']['argument_bytes']}")
            continue
        meas = measured_peak_bytes(art["memory"])
        rel = abs(rep.peak_bytes - meas) / meas
        worst = max(worst, rel)
        if rel > PARITY_REL_TOL:
            failures.append(f"{cell}: peak rel err {rel:.2f}")
    assert not failures, failures
    assert len(cells) >= 32          # the sweep, not a stray file
    assert worst <= PARITY_REL_TOL


def test_capacity_serving_mode_and_mesh_forms():
    cfg = smoke_config(get_arch("minicpm-2b"))
    rep = capacity(cfg, n_slots=4, max_len=256, recipe="decode",
                   param_dtype="bfloat16")
    assert isinstance(rep, CapacityReport)
    assert rep.kind == "decode" and rep.fits
    assert rep.cache_bytes > 0
    assert rep.peak_bytes >= rep.argument_bytes
    # paged form accounts the pool, not per-slot windows
    paged = capacity(cfg, n_slots=4, max_len=256, recipe="decode",
                     page_budget=40, page_size=32,
                     param_dtype="bfloat16")
    assert any("paged" in n for n in paged.notes)
    # mesh given as a dict divides the cache
    sh = capacity(cfg, n_slots=4, max_len=256, recipe="decode",
                  mesh={"data": 2, "model": 2}, param_dtype="bfloat16")
    assert sh.cache_bytes < rep.cache_bytes
    j = rep.to_json()
    assert j["fits"] is True and j["kind"] == "decode"


def test_capacity_overflow_detected():
    cfg = smoke_config(get_arch("minicpm-2b"))
    rep = serve_preflight(cfg, n_slots=512, max_len=32768,
                          hbm_gb=0.05)
    assert not rep.fits
    assert rep.utilization > 1.0


# ======================================================================
# liveness: the walk + the contract guards
# ======================================================================
def test_jaxpr_peak_counts_live_bytes():
    import jax
    import jax.numpy as jnp

    def f(x):
        y = x * 2.0
        return y + 1.0

    closed = jax.make_jaxpr(f)(jnp.zeros((128,), jnp.float32))
    # x and y live together across eqn 0: 2 x 512 bytes
    assert liveness.jaxpr_peak(closed.jaxpr) == 1024


def test_jaxpr_peak_recurses_into_subjaxprs():
    import jax
    import jax.numpy as jnp

    def f(x):
        def body(c, _):
            return c + 1.0, c * 2.0
        _, ys = jax.lax.scan(body, x, None, length=4)
        return ys

    closed = jax.make_jaxpr(f)(jnp.zeros((64,), jnp.float32))
    # at least the carry + the stacked output must be live
    assert liveness.jaxpr_peak(closed.jaxpr) >= 64 * 4 * 5


def test_liveness_clean_on_preset_archs():
    for arch in ANALYSIS_PRESETS["ci"].jaxpr_archs:
        assert liveness.lint_arch(arch, max_len=64, page_size=8) == []


def test_liveness_attn_chunk_contract_matches_live_default():
    from repro.analysis.capacity import ATTN_CHUNK
    assert liveness._dryrun_attn_chunk_default() == ATTN_CHUNK


# ======================================================================
# sanitize_spec drop recorder (the satellite fix)
# ======================================================================
def test_spec_drop_recorder_reasons():
    from jax.sharding import PartitionSpec as P

    from repro.dist.sharding import (reset_spec_drops, sanitize_spec,
                                     spec_drop_count, spec_drops)

    mesh = _ProxyMesh({"data": 2, "model": 4})
    reset_spec_drops()
    assert spec_drop_count() == 0

    s = sanitize_spec(P("model"), (6,), mesh, path="leaf_a")
    assert tuple(s) == ()
    assert spec_drop_count("indivisible") == 1
    d = spec_drops()[0]
    assert (d.path, d.axis, d.dim, d.reason) == \
        ("leaf_a", "model", 6, "indivisible")
    assert dict(d.mesh_sizes) == {"data": 2, "model": 4}

    sanitize_spec(P("pod"), (8,), mesh)
    assert spec_drop_count("missing-axis") == 1

    sanitize_spec(P("model", "model"), (4, 4), mesh)
    assert spec_drop_count("axis-reused") == 1
    assert spec_drop_count() == 3
    reset_spec_drops()
    assert spec_drop_count() == 0 and spec_drops() == ()


def test_param_sharding_tree_records_leaf_paths():
    import jax
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh

    from repro.dist.sharding import (RECIPES, param_sharding_tree,
                                     reset_spec_drops, spec_drops)

    abstract = {"w": jax.ShapeDtypeStruct((6, 8), jnp.float32)}
    axes = {"w": ("vocab", "embed")}     # vocab -> model(4): 6 % 4 != 0
    reset_spec_drops()
    param_sharding_tree(axes, RECIPES["WS"],
                        AbstractMesh((2, 4), ("data", "model")),
                        abstract)
    drops = [d for d in spec_drops() if d.reason == "indivisible"]
    assert len(drops) == 1 and "'w'" in drops[0].path


# ======================================================================
# sharding_prop
# ======================================================================
def test_unknown_axis_rule_on_doctored_recipe(monkeypatch):
    from repro.dist import sharding as dist_sharding

    bad = dist_sharding.Recipe("bad", {"heads": ("nonexistent_axis",)})
    monkeypatch.setattr(dist_sharding, "RECIPES",
                        {**dist_sharding.RECIPES, "bad": bad})
    found = sharding_prop.find_unknown_axes()
    assert _rule_ids(found) == ["shard-unknown-mesh-axis"]
    assert all("bad" in f.location.symbol for f in found)


def test_live_recipes_name_only_known_axes():
    assert sharding_prop.find_unknown_axes() == []
    assert set(sharding_prop.known_mesh_axes()) == {"pod", "data", "model"}


def test_sharding_prop_finds_chatglm3_kv_head_indivisibility():
    """chatglm3 has 2 KV heads: nothing about its KV cache divides a
    16-way model axis — the pass must surface the silent replication."""
    from repro.configs import get_shape
    from repro.launch.presets import FULL

    cfg = get_arch("chatglm3-6b")
    found = sharding_prop.propagate_cell(
        cfg, "single", FULL.mesh_spec("single").axis_sizes(), "decode",
        get_shape("decode_32k"), replicated_floor=2 << 30, seen=set())
    ids = _rule_ids(found)
    assert "shard-spec-dropped" in ids
    # the synthesized paged pool replicates wholesale -> info, not gate
    rep = [f for f in found if f.rule_id == "shard-replicated-large"]
    assert rep and all(f.severity == "info" for f in rep)


def test_sharding_prop_pass_clean_of_errors():
    ctx = AnalysisContext(preset=ANALYSIS_PRESETS["ci"], root=REPO)
    found = sharding_prop.run_pass(ctx)
    assert [f for f in found if f.severity == "error"] == []
    # the known paper-scale indivisibilities ARE reported
    assert "shard-spec-dropped" in _rule_ids(found)


# ======================================================================
# Baseline ratchet
# ======================================================================
def _finding(rule, sev):
    return Finding(rule, sev, Location(symbol="x"), "m")


def test_gate_counts_ignore_info():
    counts = gate_counts([_finding("a", "error"), _finding("a", "warning"),
                          _finding("b", "info")])
    assert counts == {"a": 2}


def test_baseline_regressions_ratchet():
    assert baseline_regressions({"a": 2}, {"a": 1}) == ["a: 1 -> 2"]
    assert baseline_regressions({"a": 1}, {"a": 1}) == []
    assert baseline_regressions({}, {"a": 3}) == []        # debt paid off
    assert baseline_regressions({"new": 1}, {}) == ["new: 0 -> 1"]


def test_baseline_roundtrip_and_report_fallback(tmp_path):
    rep = Report(preset="ci",
                 findings=[_finding("a", "error"), _finding("b", "info")])
    p = rep.write_baseline(str(tmp_path / "baseline.json"))
    assert load_baseline(p) == {"a": 1}
    # a full report.json is tolerated as a baseline
    p2 = rep.write(str(tmp_path / "report.json"))
    assert load_baseline(p2) == {"a": 1}


def test_committed_baseline_loads_and_is_clean():
    path = os.path.join(REPO, "artifacts", "analysis", "baseline.json")
    assert os.path.exists(path), "commit artifacts/analysis/baseline.json"
    assert load_baseline(path) == {}     # live tree carries no debt


# ======================================================================
# CLI: --output / --baseline / --write-baseline
# ======================================================================
def _cli(tmp_path, *extra):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu", REPRO_ARTIFACT_DIR=str(tmp_path))
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--rules",
         "ast-salted-hash,ast-env-mutation,ast-axis-shape-guess",
         *extra],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)


def test_cli_output_and_baseline_flags(tmp_path):
    out = tmp_path / "custom.json"
    base = tmp_path / "base.json"
    r = _cli(tmp_path, "--output", str(out),
             "--write-baseline", str(base), "--baseline", str(base))
    assert r.returncode == 0, r.stdout + r.stderr
    assert json.load(open(out))["pass"] is True
    assert "gate_counts" in json.load(open(base))
    assert "0 regressed rules" in r.stdout


def test_cli_baseline_regression_fails(tmp_path):
    base = tmp_path / "strict_base.json"
    # a baseline claiming negative debt: any finding regresses it...
    base.write_text(json.dumps(
        {"version": 1, "preset": "ci", "gate_counts": {}}))
    r = _cli(tmp_path, "--baseline", str(base))
    # ...but the ast rules are clean on the live tree, so this passes
    assert r.returncode == 0, r.stdout + r.stderr
    # and a missing baseline file is a usage error, not a crash
    r2 = _cli(tmp_path, "--baseline", str(tmp_path / "missing.json"))
    assert r2.returncode == 2


# ======================================================================
# serve --preflight (subprocess: the gate runs before any allocation)
# ======================================================================
def _serve(args, timeout=240):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", *args],
        cwd=REPO, env=env, capture_output=True, text=True,
        timeout=timeout)


def test_preflight_rejects_oversized_config_naming_rule():
    # 512 slots x 32k tokens of contiguous cache against a 0.05 GiB
    # budget: must exit nonzero BEFORE trying to allocate any of it
    r = _serve(["--arch", "minicpm-2b", "--smoke", "--preflight",
                "--slots", "512", "--max-len", "32768",
                "--hbm-gb", "0.05", "--requests", "0"])
    assert r.returncode != 0
    assert "capacity-hbm-overflow" in r.stderr
    assert "predicted peak" in r.stdout      # the report printed first


def test_preflight_passes_fitting_config():
    r = _serve(["--arch", "minicpm-2b", "--smoke", "--preflight",
                "--slots", "2", "--max-len", "64", "--max-new", "4",
                "--requests", "2"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "preflight: predicted peak" in r.stdout
    assert "served 2/2" in r.stdout


def test_preflight_paged_config():
    r = _serve(["--arch", "minicpm-2b", "--smoke", "--preflight",
                "--slots", "2", "--max-len", "64", "--max-new", "4",
                "--requests", "0", "--page-size", "16"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "preflight: predicted peak" in r.stdout
