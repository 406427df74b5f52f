"""Trace-to-metric reduction: by hand on a made-up trace, and on a small
trace recorded on a TPU v5e (``data/trace_code.json``: the first two
engine steps of a traced ``starcoder2-3b.code`` window, ops of 20 us and
longer)."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import devtrace  # noqa: E402
import run  # noqa: E402

MS = 1_000_000          # ns


def _rec(chips=1):
    cell = run.Cell(name="x", chips=chips, config={}, traffic={},
                    metrics=[])
    return run.RunRecord(cell=cell, seed=0, seconds=0.1, loop="open")


def _made_up():
    """A 100 ms window with two steps. Step 1 (10-40 ms) runs a decode
    program 15-35 ms holding a loop op 15-35 with two body ops; step 2
    (50-90 ms) runs a prefill 52-70 and a decode 72-88."""
    ops = [[15 * MS, 35 * MS, "%while.1 = (s32[], bf16[4]{0}) while()"],
           [16 * MS, 20 * MS, "%fusion.2 = bf16[4,8]{1,0} fusion()"],
           [22 * MS, 30 * MS, "%fusion.3 = f32[4]{0} fusion()"],
           [52 * MS, 70 * MS, "%convolution.4 = bf16[1,64]{1,0} conv()"],
           [72 * MS, 88 * MS, "%fusion.2 = bf16[4,8]{1,0} fusion()"]]
    modules = [[15 * MS, 35 * MS, "jit__step_fn(1)"],
               [52 * MS, 70 * MS, "jit__prefill_fn(2)"],
               [72 * MS, 88 * MS, "jit__step_fn(1)"]]
    host = [[0, 100 * MS, "window"],
            [10 * MS, 40 * MS, "engine.step"],
            [11 * MS, 14 * MS, "PjitFunction(step)"],
            [36 * MS, 39 * MS, "np.asarray"],
            [40 * MS, 50 * MS, "generator.wait"],
            [50 * MS, 90 * MS, "engine.step"]]
    return {"host": host,
            "devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}}


def test_made_up_trace_by_hand():
    s = devtrace.summarize(_made_up(), _rec())
    assert s["window_s"] == pytest.approx(0.100)
    assert s["busy_s"] == pytest.approx(0.020 + 0.018 + 0.016)
    (a, b) = s["steps"]
    assert a["busy_s"] == pytest.approx(0.020)
    assert a["decode_s"] == pytest.approx(0.020) and a["prefill_s"] == 0
    assert b["busy_s"] == pytest.approx(0.034)
    assert b["decode_s"] == pytest.approx(0.016)
    assert b["prefill_s"] == pytest.approx(0.018)
    ops = dict(s["breakdown"]["device_ops"])
    # the loop's own time is net of its body: 20 - 4 - 8 ms
    assert ops["while.1 s32[]"] == pytest.approx(0.008)
    assert ops["fusion.2 bf16[4,8]"] == pytest.approx(0.004 + 0.016)
    idle = dict(s["breakdown"]["idle_gaps"])
    assert idle == pytest.approx({
        "window/host": 0.010 + 0.010,               # 0-10, 90-100
        "engine.step/PjitFunction(step)": 0.003,    # 11-14
        "engine.step/np.asarray": 0.003,            # 36-39
        "generator.wait/host": 0.010,               # 40-50
        # 10-11, 14-15, 35-36, 39-40, 50-52, 70-72, 88-90
        "engine.step/host": 0.001 * 4 + 0.002 * 3})


def test_readers_on_made_up_trace():
    rec = _rec()
    rec.trace = devtrace.summarize(_made_up(), rec)
    host = run.metric_reader("host_ms_per_step")(rec)
    assert host == pytest.approx(((30 - 20) + (40 - 34)) / 2)
    assert run.metric_reader("decode_step_ms")(rec) == pytest.approx(18.0)
    rec.prefill_tokens = 2000
    assert run.metric_reader("prefill_ms_per_ktok")(rec) == \
        pytest.approx(9.0)
    assert run.metric_reader("device_idle_share")(rec) == \
        pytest.approx(46.0)


def test_union_and_cover():
    m = devtrace.union([[5, 9], [1, 3], [2, 4], [8, 12]])
    assert m == [(1, 4), (5, 12)]
    starts = [a for a, _ in m]
    assert devtrace.covered(m, starts, 0, 100) == 10
    assert devtrace.covered(m, starts, 3, 6) == 2


def test_recorded_trace():
    """Step 1 admits a 2048-bucket prompt: one prefill (96.355 ms) and a
    decode (30.982 ms); step 2 decodes (30.978 ms). The device clock runs
    about 0.24 ms ahead of the host's here: step 2's decode starts
    before its span, and still belongs to it."""
    trace = json.loads((HERE / "data" / "trace_code.json").read_text())
    s = devtrace.summarize(trace, _rec())
    one, two = s["steps"]
    assert one["prefill_s"] == pytest.approx(0.096355, abs=1e-6)
    assert one["decode_s"] == pytest.approx(0.030982, abs=1e-6)
    assert two["prefill_s"] == 0
    assert two["decode_s"] == pytest.approx(0.030978, abs=1e-6)
    assert 0 < s["busy_s"] < s["window_s"] == pytest.approx(0.180735262)
    for st in (one, two):
        span = (st["t1"] - st["t0"]) / 1e9
        assert 0 < st["busy_s"] <= span
        assert st["decode_s"] + st["prefill_s"] <= span
    ops = dict(s["breakdown"]["device_ops"])
    assert "fusion.173 bf16[4096,16,2,128]" in ops      # the page gather
    idle = dict(s["breakdown"]["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(s["window_s"] - s["busy_s"])
