"""Static-analysis subsystem coverage.

Every seeded-violation fixture asserts *its* rule id fires (the
acceptance contract: race, coverage, VMEM, vjp, dtype, hash(),
env-mutation, axis-guess), the clean tree passes ``--strict``, and the
dispatch registration hook rejects a broken kernel with the finding
message before it can corrupt anything at runtime.
"""
import json
import math
import os
import subprocess
import sys
import textwrap

import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.analysis import RULES, Finding, Location, Report, run_analysis
from repro.analysis.ast_lint import lint_source
from repro.analysis.contracts import (check_axis_resolvable,
                                      check_cache_axes,
                                      check_dispatch_closure)
from repro.analysis.findings import apply_suppressions, parse_suppressions
from repro.analysis.jaxpr_lint import predict_prefill_compiles, scan_jaxpr
from repro.analysis.kernel_validator import (capture_pallas_calls,
                                             check_capture,
                                             validate_impl)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rule_ids(findings):
    return sorted({f.rule_id for f in findings})


# ======================================================================
# Findings / report model
# ======================================================================
def test_finding_severity_validated():
    with pytest.raises(ValueError):
        Finding("kernel-write-race", "fatal", Location(), "boom")


def test_report_exit_codes():
    r = Report(preset="ci")
    assert r.exit_code() == 0 and r.exit_code(strict=True) == 0
    r.findings.append(Finding("jaxpr-wide-dot", "info", Location(), "i"))
    assert r.exit_code(strict=True) == 0           # info never fails
    r.findings.append(Finding("analysis-suppression", "warning",
                              Location(), "w"))
    assert r.exit_code() == 0 and r.exit_code(strict=True) == 1
    r.findings.append(Finding("ast-salted-hash", "error", Location(), "e"))
    assert r.exit_code() == 1


def test_report_json_schema(tmp_path):
    r = Report(preset="ci")
    r.findings.append(Finding(
        "ast-salted-hash", "error",
        Location(file="src/x.py", line=3), "msg", "fix"))
    path = r.write(str(tmp_path / "report.json"))
    payload = json.load(open(path))
    assert payload["version"] == 1
    assert payload["counts"] == {"error": 1, "warning": 0, "info": 0}
    assert payload["by_rule"] == {"ast-salted-hash": 1}
    assert payload["pass"] is False
    f = payload["findings"][0]
    assert set(f) == {"rule_id", "severity", "file", "line", "symbol",
                      "message", "suggestion"}


# ======================================================================
# Suppression
# ======================================================================
def test_justified_suppression_drops_finding():
    src = "x = hash(key)  # repro: ignore[ast-salted-hash] -- key is process-local\n"
    assert lint_source(src, "m.py") == []


def test_unjustified_suppression_is_inactive_and_flagged():
    src = "x = hash(key)  # repro: ignore[ast-salted-hash]\n"
    found = lint_source(src, "m.py")
    ids = rule_ids(found)
    assert "ast-salted-hash" in ids            # still fires
    assert "analysis-suppression" in ids       # and the waiver is called out


def test_suppression_is_rule_specific():
    src = "x = hash(key)  # repro: ignore[ast-env-mutation] -- wrong rule named\n"
    assert "ast-salted-hash" in rule_ids(lint_source(src, "m.py"))


def test_parse_suppressions():
    supp = parse_suppressions(
        "a = 1\nb = 2  # repro: ignore[r-one, r-two] -- because reasons\n")
    assert supp[2].rule_ids == ("r-one", "r-two")
    assert supp[2].justified


# ======================================================================
# AST lint: the three shipped bug classes
# ======================================================================
def test_ast_salted_hash_fixture():
    found = lint_source("key = hash((arch, shape))\n", "f.py")
    assert rule_ids(found) == ["ast-salted-hash"]
    assert found[0].location.line == 1


def test_ast_env_mutation_fixture():
    # the XLA_FLAGS bug class: import-time env mutation
    bad = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    """)
    assert rule_ids(lint_source(bad, "f.py")) == ["ast-env-mutation"]
    assert rule_ids(lint_source(
        'import os\nos.environ.setdefault("XLA_FLAGS", "x")\n', "f.py")) \
        == ["ast-env-mutation"]


def test_ast_env_mutation_allowed_in_function_and_main():
    ok = textwrap.dedent("""
        import os
        def force():
            os.environ["XLA_FLAGS"] = "x"
        if __name__ == "__main__":
            os.environ["XLA_FLAGS"] = "y"
    """)
    assert lint_source(ok, "f.py") == []


def test_ast_axis_shape_guess_fixture():
    # the _splice bug class: axis identified by extent collision
    bad = textwrap.dedent("""
        def splice(big, small):
            if big.shape[0] == small.shape[0]:
                return 0
    """)
    assert rule_ids(lint_source(bad, "f.py")) == ["ast-axis-shape-guess"]
    # rank/shape comparisons stay legal
    ok = "def f(a, b):\n    return a.shape == b.shape\n"
    assert lint_source(ok, "f.py") == []


def test_analyzer_names_ast_rules_on_seeded_tree(tmp_path):
    """End-to-end through the runner: a tree seeding all three bug
    classes exits non-zero naming each rule id."""
    pkg = tmp_path / "src" / "repro"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--flag"
        KEY = hash("persisted")
        def splice(big, small):
            return big.shape[0] == small.shape[0]
    """))
    report = run_analysis(
        "ci", rules=["ast-salted-hash", "ast-env-mutation",
                     "ast-axis-shape-guess"], root=str(tmp_path))
    assert report.exit_code() == 1
    assert set(report.by_rule()) == {"ast-salted-hash", "ast-env-mutation",
                                     "ast-axis-shape-guess"}


# ======================================================================
# Kernel validator: seeded-violation fixture kernels
# ======================================================================
def _block_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


X32 = jax.ShapeDtypeStruct((32, 8), jnp.float32)


def _pallas_fixture(grid, in_map, out_map, out_shape, in_block=(8, 8),
                    out_block=(8, 8), kernel=_block_kernel):
    def fn(x, **_):
        return pl.pallas_call(
            kernel, grid=grid,
            in_specs=[pl.BlockSpec(in_block, in_map)],
            out_specs=pl.BlockSpec(out_block, out_map),
            out_shape=out_shape, interpret=True)(x)
    return fn


def test_fixture_write_race():
    """Every grid cell writes block (0, 0); no scratch, no output read."""
    fn = _pallas_fixture((4,), lambda i: (i, 0), lambda i: (0, 0),
                         jax.ShapeDtypeStruct((8, 8), jnp.float32))
    found = validate_impl("op", "pallas", fn, [X32], {},
                          ref=lambda x, **_: x[:8] * 2)
    assert rule_ids(found) == ["kernel-write-race"]


def test_fixture_grid_coverage():
    """Grid (1,) over a 2-block output: half stays uninitialized."""
    fn = _pallas_fixture((1,), lambda i: (i, 0), lambda i: (i, 0),
                         jax.ShapeDtypeStruct((16, 8), jnp.float32))
    found = validate_impl("op", "pallas", fn, [X32], {},
                          ref=lambda x, **_: jnp.tile(x[:8] * 2, (2, 1)))
    assert rule_ids(found) == ["kernel-grid-coverage"]


def test_fixture_vmem_budget():
    """One 4096x4096 f32 block in and out: 256 MiB double-buffered."""
    big = jax.ShapeDtypeStruct((4096, 4096), jnp.float32)
    fn = _pallas_fixture((1,), lambda i: (0, 0), lambda i: (0, 0), big,
                         in_block=(4096, 4096), out_block=(4096, 4096))
    found = validate_impl("op", "pallas", fn, [big], {},
                          ref=lambda x, **_: x * 2)
    assert rule_ids(found) == ["kernel-vmem-budget"]


def test_fixture_missing_vjp():
    found = validate_impl("op", "pallas", lambda x, **_: x * 2, [X32], {},
                          ref=None)
    assert rule_ids(found) == ["kernel-missing-vjp"]


def test_fixture_dtype_parity():
    @jax.custom_vjp
    def widened(x):
        return x.astype(jnp.float32) * 2

    widened.defvjp(lambda x: (widened(x), None),
                   lambda _, ct: (ct.astype(jnp.bfloat16) * 2,))
    xb = jax.ShapeDtypeStruct((8, 8), jnp.bfloat16)
    found = validate_impl("op", "pallas", widened, [xb], {},
                          ref=lambda x, **_: x * 2)
    assert rule_ids(found) == ["kernel-dtype-parity"]


def test_fixture_trace_error():
    def broken(x, **_):
        raise ValueError("bad block size")

    found = validate_impl("op", "pallas", broken, [X32], {},
                          ref=lambda x, **_: x)
    assert rule_ids(found) == ["kernel-trace-error"]


def test_accumulation_exemptions():
    """Revisiting an output block is legal with a scratch carry or an
    output-ref read (the ssd_scan and paged_attention patterns)."""
    import jax.experimental.pallas.tpu as pltpu

    out8 = jax.ShapeDtypeStruct((8, 8), jnp.float32)

    def scratch_kernel(x_ref, o_ref, acc_ref):
        acc_ref[...] += x_ref[...]
        o_ref[...] = acc_ref[...]

    def with_scratch(x, **_):
        return pl.pallas_call(
            scratch_kernel, grid=(4,),
            in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 8), lambda i: (0, 0)),
            out_shape=out8,
            scratch_shapes=[pltpu.VMEM((8, 8), jnp.float32)],
            interpret=True)(x)

    def rmw_kernel(x_ref, o_ref):
        o_ref[...] = o_ref[...] + x_ref[...]

    ref = lambda x, **_: x[:8] * 4  # noqa: E731
    found = validate_impl("op", "pallas", with_scratch, [X32], {}, ref=ref)
    assert found == []
    rmw = _pallas_fixture((4,), lambda i: (i, 0), lambda i: (0, 0), out8,
                          kernel=rmw_kernel)
    found = validate_impl("op", "pallas", rmw, [X32], {}, ref=ref)
    assert found == []


def test_capture_records_live_kernels():
    """The spy sees through the jitted ops wrappers and normalizes the
    PrefetchScalarGridSpec form (paged attention's scalar page table,
    row-mask words and live-page counts), and the static checks read
    the pool left in HBM and the kernel's DMA scratch."""
    import functools

    from repro.kernels.dispatch import implementations

    fn = implementations("paged_decode_attention")["pallas"]
    q = jax.ShapeDtypeStruct((2, 4, 32), jnp.float32)
    kp = jax.ShapeDtypeStruct((9, 8, 2, 32), jnp.float32)
    pt = jax.ShapeDtypeStruct((2, 4), jnp.int32)
    mk = jax.ShapeDtypeStruct((2, 32), jnp.bool_)
    with capture_pallas_calls() as caps:
        jax.eval_shape(functools.partial(fn, pages_per_block=2),
                       q, kp, kp, pt, mk)
    assert len(caps) == 1
    cap = caps[0]
    assert cap.num_scalar_prefetch == 3     # table, mask words, live pages
    assert cap.grid == (2,)                 # one slot per grid step
    # the pools stay in HBM; two pages a block, double-buffered in VMEM
    assert [getattr(s.memory_space, "value", None)
            for s in cap.in_specs[1:]] == ["any", "any"]
    kbuf, vbuf, sems, buf = cap.scratch_shapes
    assert kbuf.shape == vbuf.shape == (2, 2, 8, 2, 32)
    assert sems.shape == (2, 2) and buf.shape == (1,)
    assert check_capture(cap, vmem_budget=16 * 2**20, label="t") == []
    # a budget the two float32 page buffers alone fill is still refused
    page_bufs = 2 * 4 * math.prod(kbuf.shape)
    assert rule_ids(check_capture(cap, vmem_budget=page_bufs,
                                  label="t")) == ["kernel-vmem-budget"]


# ======================================================================
# Contract checker (injectable fixtures + the live-tree invariants)
# ======================================================================
def test_contract_cache_axes_fixture():
    spec = {"k": ((2, 4, 8), "bfloat16"), "extra": ((2,), "int32")}
    axes = {"k": (None, "batch")}              # wrong rank; extra missing
    found = check_cache_axes(spec, axes, axes_name="CACHE_AXES", symbol="t")
    assert rule_ids(found) == ["contract-cache-axes"]
    assert len(found) == 2


def test_contract_axis_unresolvable_fixture():
    from repro.dist.sharding import Recipe

    recipes = {"WS": Recipe("WS", {"batch": None})}
    found = check_axis_resolvable({"k": ("batch", "kv_seq")}, recipes,
                                  source="t")
    assert rule_ids(found) == ["contract-axis-unresolvable"]
    assert "kv_seq" in found[0].message


def test_contract_dispatch_closure_fixture():
    from repro.kernels.tune import TUNE_PRESETS

    table = {"mystery_op": {"pallas": lambda: None}}   # no xla ref
    found = check_dispatch_closure(("mystery_op",), table, TUNE_PRESETS,
                                   calib_kinds={})
    ids = rule_ids(found)
    assert ids == ["contract-calib-kind", "contract-dispatch-ref",
                   "contract-tune-grid"]


def test_live_kv_seq_axis_is_declared():
    """REGRESSION (rule: contract-axis-unresolvable): CACHE_AXES names
    the ``kv_seq`` axis but no sharding recipe declared it, so
    ``Recipe.spec_for`` silently replicated — now declared replicate-
    by-design in every recipe."""
    from repro.dist.sharding import RECIPES
    from repro.models.model import CACHE_AXES, PAGED_CACHE_AXES

    for axes in (CACHE_AXES, PAGED_CACHE_AXES):
        assert check_axis_resolvable(axes, RECIPES, source="live") == []
    assert all("kv_seq" in r.rules for r in RECIPES.values())


# ======================================================================
# jaxpr lint
# ======================================================================
def test_predict_prefill_compiles_unit():
    from repro.configs import ARCHS, smoke_config
    from repro.serve import Scheduler

    cfg = smoke_config(ARCHS["minicpm-2b"])
    s = Scheduler(cfg=cfg, max_len=64)
    # lengths 3..16 land on buckets {8, 16} at width 1
    assert predict_prefill_compiles(s, range(3, 17)) == 2
    assert predict_prefill_compiles(s, range(3, 17), widths=(1, 2)) == 4
    assert predict_prefill_compiles(s, range(1, 65)) \
        <= s.max_prefill_compiles()


def test_scan_jaxpr_flags_host_sync():
    def noisy(x):
        jax.debug.print("x={x}", x=x.sum())
        return x * 2

    closed = jax.make_jaxpr(noisy)(jnp.ones((4,)))
    found = scan_jaxpr(closed, label="t", rt_dtype="float32")
    assert "jaxpr-host-sync" in rule_ids(found)


def test_scan_jaxpr_flags_f64():
    with jax.enable_x64():
        closed = jax.make_jaxpr(
            lambda x: x.astype(jnp.float64) * 2)(jnp.ones((4,)))
    found = scan_jaxpr(closed, label="t", rt_dtype="float32")
    assert rule_ids(found) == ["jaxpr-dtype-widen"]


def test_scan_jaxpr_wide_dot_is_info_only():
    closed = jax.make_jaxpr(
        lambda a, b: jnp.dot(a, b, preferred_element_type=jnp.float32))(
        jnp.ones((4, 4), jnp.bfloat16), jnp.ones((4, 4), jnp.bfloat16))
    found = scan_jaxpr(closed, label="t", rt_dtype="bfloat16")
    assert rule_ids(found) == ["jaxpr-wide-dot"]
    assert all(f.severity == "info" for f in found)


# ======================================================================
# Registration-time validation hook
# ======================================================================
def _example():
    return [X32], {}


def _racy(x, **_):
    return pl.pallas_call(
        _block_kernel, grid=(4,),
        in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 8), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, 8), x.dtype),
        interpret=True)(x)


def test_register_impl_rejects_broken_kernel():
    from repro.kernels.dispatch import (KernelValidationError,
                                        implementations, register_impl)

    table = implementations("rmsnorm")
    assert "bad_fixture" not in table
    try:
        with pytest.raises(KernelValidationError, match="kernel-write-race"):
            register_impl("rmsnorm", "bad_fixture",
                          example=_example)(_racy)
        assert "bad_fixture" not in table      # rejected, not registered
        # explicit opt-out (the fixture-seeding escape hatch)
        register_impl("rmsnorm", "bad_fixture", example=_example,
                      validate=False)(_racy)
        assert table["bad_fixture"] is _racy
    finally:
        table.pop("bad_fixture", None)


def test_register_impl_env_opt_out(monkeypatch):
    from repro.kernels.dispatch import implementations, register_impl

    monkeypatch.setenv("REPRO_VALIDATE_KERNELS", "0")
    table = implementations("rmsnorm")
    try:
        register_impl("rmsnorm", "bad_fixture", example=_example)(_racy)
        assert "bad_fixture" in table
    finally:
        table.pop("bad_fixture", None)


def test_tune_refuses_to_time_broken_kernels():
    """run_tuning(validate=True) fails before timing anything when a
    registered impl flunks the validator."""
    from repro.kernels.dispatch import (KernelValidationError,
                                        implementations, register_impl)
    from repro.kernels.tune import CI, run_tuning

    table = implementations("rmsnorm")
    try:
        register_impl("rmsnorm", "bad_fixture", validate=False)(_racy)
        with pytest.raises(KernelValidationError):
            run_tuning(CI, cells=[("minicpm-2b", "prefill_32k")],
                       validate=True)
    finally:
        table.pop("bad_fixture", None)


# ======================================================================
# Clean tree + CLI
# ======================================================================
def test_clean_tree_full_ci_preset():
    """The acceptance gate, in-process: every pass over the live tree,
    zero errors and zero warnings (info findings are allowed)."""
    report = run_analysis("ci")
    counts = report.counts()
    assert counts["error"] == 0, [f.describe() for f in report.findings
                                  if f.severity == "error"]
    assert counts["warning"] == 0, [f.describe() for f in report.findings
                                    if f.severity == "warning"]
    assert set(report.passes) == {"ast_lint", "contracts",
                                  "kernel_validator", "jaxpr_lint",
                                  "liveness", "sharding_prop",
                                  "spmd_lint", "deploy_lint"}
    assert report.ok(strict=True)


def test_cli_strict_exits_zero_on_clean_rules(tmp_path):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu", REPRO_ARTIFACT_DIR=str(tmp_path))
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--strict", "--rules",
         "ast-salted-hash,ast-env-mutation,ast-axis-shape-guess,"
         "contract-cache-axes,contract-axis-unresolvable,"
         "contract-dispatch-ref,contract-tune-grid,contract-calib-kind"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    payload = json.load(open(tmp_path / "analysis" / "report.json"))
    assert payload["pass"] is True and payload["strict_pass"] is True
    # the rules filter skipped the jax-heavy passes entirely
    assert set(payload["passes"]) == {"ast_lint", "contracts"}


def test_cli_list_rules():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--list-rules"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0
    for rid in RULES:
        assert rid in r.stdout


def test_unknown_rule_rejected():
    with pytest.raises(KeyError):
        run_analysis("ci", rules=["no-such-rule"])
    with pytest.raises(KeyError):
        run_analysis("nope")


def test_register_pass_validates_rule_ids():
    from repro.analysis.registry import register_pass

    with pytest.raises(KeyError):
        register_pass("bogus", rules=("not-a-rule",))(lambda ctx: [])
