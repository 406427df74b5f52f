"""The harness on the CPU: the one command refuses to report without a
chip; a tiny cell driven through the rest of a run is correct, and is
not correct once the timed path is broken underneath."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
sys.path.insert(0, str(CHIP))

import faults  # noqa: E402
import run  # noqa: E402

TINY_ARCH = "tiny-starcoder2"


def test_command_without_a_chip_prints_no_result(tmp_path):
    """A CPU-only machine: the device check fails and no number is
    reported under a device metric."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    p = subprocess.run(
        [sys.executable, str(CHIP / "run.py"), "--workload",
         "starcoder2-3b.code", "--seed", str(2 ** 33 + 1), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    """A tiny starcoder2 cell on the CPU; JAX's global settings that a
    run changes are put back afterwards."""
    import jax
    from repro import configs
    cfg = configs.smoke_config(configs.get_arch("starcoder2-3b")).replace(
        name=TINY_ARCH)
    real = configs.get_arch
    monkeypatch.setattr(configs, "get_arch",
                        lambda n: cfg if n == TINY_ARCH else real(n))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    saved = jax.config.jax_persistent_cache_min_compile_time_secs
    metrics = [dict(name=n, unit="u", _kind="end_to_end")
               for n in ("ttft_p90_ms", "tpot_p95_ms", "setup_s")]
    yield run.Cell(
        name="tiny.code", chips=1,
        config=json.loads((HERE / "data" / "tiny.json").read_text()),
        traffic=json.loads((HERE / "data" / "tiny_traffic.json")
                           .read_text()), metrics=metrics)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved)


@pytest.mark.parametrize("fault", (None,) + faults.KINDS)
def test_tiny_run_is_correct_until_broken(tiny, fault):
    with faults.planted(fault):
        out = run.run(tiny, seed=2 ** 33 + 11, seconds=1.5, trace=False,
                      device_check=False)
    gap = out["checks"]["max_logit_gap"]
    assert out["attempted"] == 225 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p90_ms", "tpot_p95_ms", "setup_s"}
    assert out["correct"] is (fault is None), gap
    assert list(out)[-1] == "checks"


def test_reference_weights_are_the_programs(tiny):
    """The reference makes the same weights from the seed as the
    program's initialiser, by its own code."""
    import jax
    from repro.configs import get_arch
    from repro.launch.serve import init_serving_params
    seed = run.param_seed(2 ** 33 + 11)
    prog = init_serving_params(get_arch(TINY_ARCH), seed, "bfloat16")
    ref = run.architecture(tiny.config).make_weights(tiny.config["model"],
                                                     seed, "bfloat16")
    flat = {jax.tree_util.keystr(p): v for p, v in
            jax.tree_util.tree_flatten_with_path(prog)[0]}
    assert sorted(flat) == sorted(ref)
    for k, v in flat.items():
        assert v.dtype == ref[k].dtype
        np.testing.assert_array_equal(np.asarray(v, np.float32),
                                      np.asarray(ref[k], np.float32))


def test_fp8_control_is_not_correct(tiny):
    """The control, the reference one precision below the configuration
    (float8 matrix products), fails the limit that the program meets."""
    seed = 2 ** 31 + 5
    rec = run.measure(tiny, seed, 1.0, trace=False, device_check=False)
    sound = run.check_served(rec, seed)
    control = run.check_served(rec, seed, control="fp8")
    assert sound["tokens"] == control["tokens"] >= 64
    assert sound["max_logit_gap"] <= sound["limit"] < \
        control["max_logit_gap"]
