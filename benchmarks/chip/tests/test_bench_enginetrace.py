"""The engine's spans and scopes in a trace: ``enginetrace``'s reduction
and the readers of the metrics it feeds, by hand on a made-up trace; the
harness's own metrics unmoved by the engine's spans."""
import json
import math
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
sys.path.insert(0, str(CHIP))

import devtrace  # noqa: E402
import enginetrace  # noqa: E402
import peaks  # noqa: E402
import run  # noqa: E402

MS = 1_000_000          # ns
STEP = "jit(_step_fn)/while/body/closed_call"


def _costs():
    config = json.loads((CHIP / "configs" / "starcoder2-3b.json")
                        .read_text())
    return run.architecture(config).ModelCosts(config["model"])


def _rec():
    cell = run.Cell(name="x", chips=1, config={}, traffic={}, metrics=[])
    rec = run.RunRecord(cell=cell, seed=0, seconds=0.1, loop="open",
                        t_open=1.0, t_close=1.1)
    rec.model = _costs()
    rec.peak = peaks.peaks("TPU v5 lite")
    prompt = [0] * 1000
    for rid in (1, 2, 3):
        rec.requests[rid] = run.ReqRecord(rid=rid, due=1.0, prompt=prompt,
                                          out_len=4)
    rec.steps = [run.StepRecord(1.01, 1.04, [], {1: 1, 2: 1}, [100, 200]),
                 run.StepRecord(1.05, 1.09, [3], {1: 1, 3: 1}, [101, 1001])]
    rec.prefill_tokens = 2048
    return rec


def _made_up(spans=True):
    """``test_bench_trace``'s 100 ms window of two steps, with the spans
    the engine puts inside each step and the scopes its ops carry. Step
    1 (10-40 ms) decodes 15-35 (a loop 15-35 holding an attention op
    16-20 and an MLP op 22-30); step 2 (50-90 ms) admits a request,
    prefilled 52-70, then decodes 72-88 (the attention op again)."""
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
    if spans:
        host += [[a * MS, b * MS, n] for a, b, n in (
            (10, 11, "serve.admit"), (11, 14, "serve.decode"),
            (14, 36, "serve.wait"), (36, 39, "serve.fetch"),
            (39, 40, "serve.sample"),
            (50, 71, "serve.admit"), (50, 51, "serve.prefill"),
            (51, 70, "serve.prefill_fetch"), (70, 71, "serve.scatter"),
            (71, 72, "serve.decode"), (72, 88, "serve.wait"),
            (88, 89.5, "serve.fetch"), (89.5, 90, "serve.sample"),
            (89.6, 89.9, "serve.release"))]
        host.sort()
    return {"host": host,
            "devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}}


# the decode program's instructions and their op_name paths
NAMES = {"while.1": "jit(_step_fn)/while",
         "fusion.2": f"{STEP}/paged_decode_attention/dot_general",
         "fusion.3": f"{STEP}/mlp/dot_general"}


def _summarize(trace, rec):
    return enginetrace.summarize(trace, rec, NAMES)


def test_spans_by_hand():
    s = _summarize(_made_up(), _rec())
    got = s["spans"]
    assert set(got) == {"serve.admit", "serve.decode", "serve.wait",
                        "serve.fetch", "serve.sample", "serve.prefill",
                        "serve.prefill_fetch", "serve.scatter",
                        "serve.release"}
    assert got["serve.wait"]["count"] == 2
    assert got["serve.wait"]["host_s"] == pytest.approx(0.022 + 0.016)
    assert got["serve.wait"]["idle_s"] == pytest.approx(0.002)   # 14-15, 35-36
    assert got["serve.fetch"] == pytest.approx(
        {"count": 2, "host_s": 0.0045, "idle_s": 0.0045})
    assert got["serve.admit"] == pytest.approx(
        {"count": 2, "host_s": 0.022, "idle_s": 0.022 - 0.018})
    assert got["serve.prefill_fetch"]["idle_s"] == pytest.approx(0.001)
    assert got["serve.release"]["count"] == 1


def test_scopes_by_hand():
    s = _summarize(_made_up(), _rec())
    # the decode programs only: the prefill's op is not counted
    assert s["scopes"] == pytest.approx({
        "paged_decode_attention": 0.004 + 0.016,
        "mlp": 0.008,
        "unscoped": 0.020 - 0.004 - 0.008})   # the loop's own time
    assert sum(s["scopes"].values()) == pytest.approx(
        sum(st["decode_s"] for st in s["steps"]))


def test_op_names_from_compiled_hlo():
    """A compiled program's HLO text gives each instruction its scope
    path; ``instruction`` reads the name off a trace's op event."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("mlp"):
            return jnp.sin(x) * 2

    text = jax.jit(f).lower(jnp.ones(8)).compile().as_text()
    names = enginetrace.op_names(text)
    assert any(p.startswith("jit(f)/mlp/") for p in names.values())
    op = next(n for n, p in names.items() if "/mlp/" in p)
    assert enginetrace.instruction(f"%{op} = f32[8]{{0}} fusion()") == op
    assert enginetrace.instruction(f"{op} f32[8]") == op


def test_op_names_inherit_through_compiler_added_instructions():
    """A convert that the compiler put between the page gather and the
    attention product carries no op_name: it takes its operand's."""
    text = "\n".join([
        "ENTRY %main {",
        '  %p.1 = bf16[2]{0} parameter(0), metadata={op_name="pages"}',
        "  %fusion.150 = bf16[2]{0} fusion(%p.1), kind=kLoop, calls="
        '%fc.1, metadata={op_name="jit(_step_fn)/while/body/'
        'paged_decode_attention/gather" stack_frame_id=3}',
        "  %convert.58 = f32[2]{0} convert(%fusion.150), backend_config={}",
        "  %bitcast.2 = f32[2]{0} bitcast(%convert.58)",
        "  ROOT %add.1 = f32[2]{0} add(%bitcast.2, %bitcast.2), "
        'metadata={op_name="jit(_step_fn)/add"}',
        "}"])
    names = enginetrace.op_names(text)
    gather = "jit(_step_fn)/while/body/paged_decode_attention/gather"
    assert names == {"p.1": "pages", "fusion.150": gather,
                     "convert.58": gather, "bitcast.2": gather,
                     "add.1": "jit(_step_fn)/add"}


@pytest.mark.parametrize("path,scope", [
    ("jit(_step_fn)/while/body/closed_call/mlp/dot_general", "mlp"),
    ("jit(_step_fn)/paged_decode_attention/jit(_where)/rmsnorm/x",
     "paged_decode_attention"),
    ("jit(_step_fn)/while/body/add", "unscoped"),
    ("", "unscoped")])
def test_outermost_scope(path, scope):
    assert enginetrace.outermost(
        path, enginetrace.program_scopes()) == scope


def test_new_readers_on_made_up_trace():
    rec = _rec()
    rec.trace = _summarize(_made_up(), rec)
    assert run.metric_reader("logits_fetch_ms_per_step.serve")(rec) == \
        pytest.approx(4.5 / 2)
    m, p = rec.model, rec.peak
    bound = sum(max(m.kv_bytes_per_token * c / p["hbm_bytes_per_s"],
                    4 * m.L * m.nq * m.hd * c / p["bf16_flops"])
                for c in (100 + 200, 101 + 1001))
    assert run.metric_reader("decode_attn_roofline.serve")(rec) == \
        pytest.approx(100 * bound / 0.020)


def test_queue_wait_by_hand():
    rec = _rec()
    waits = [0.1 * (i + 1) for i in range(10)]
    for i, w in enumerate(waits):
        r = run.ReqRecord(rid=10 + i, due=1.0, prompt=[0], out_len=1)
        r.t_queued, r.t_admitted = 1.05 - w, 1.05
        rec.requests[r.rid] = r
    early = run.ReqRecord(rid=30, due=0.5, prompt=[0], out_len=1)
    early.t_queued, early.t_admitted = 0.0, 0.9     # before the window
    waiting = run.ReqRecord(rid=31, due=1.0, prompt=[0], out_len=1)
    waiting.t_queued, waiting.t_admitted = 1.02, math.nan
    rec.requests.update({30: early, 31: waiting})
    # rank 0.9 * 9 = 8.1: a tenth of the way from the 9th wait to the 10th
    assert run.metric_reader("queue_wait_p90_ms")(rec) == \
        pytest.approx(1e3 * (0.9 + 0.1 * (1.0 - 0.9)))


def test_new_readers_read_nothing_without_the_engine_instrumentation():
    """A harness that neither keeps the engine's spans and scopes nor
    copies the requests' stamps: each reader returns None, and none
    raises."""
    rec = _rec()
    rec.trace = devtrace.summarize(_made_up(), rec)
    for name in ("queue_wait_p90_ms", "logits_fetch_ms_per_step",
                 "decode_attn_roofline"):
        assert run.metric_reader(name)(rec) is None


def _existing(rec):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return {m["name"]: run.metric_reader(m["name"])(rec)
            for m in bench["per_layer"]}


def _recorded(cell):
    """A recorded trace of the engine's spans (``data/trace_<cell>_
    engine.json``: the first three engine steps of a traced window on a
    TPU v5e, ops of 20 us and longer), the run record of those steps,
    and the decode program's op names."""
    d = json.loads((HERE / "data" / f"trace_{cell}_engine.json")
                   .read_text())
    r = d.pop("record")
    config = json.loads((CHIP / "configs" / f"{CONFIGS[cell]}.json")
                        .read_text())
    rec = run.RunRecord(cell=run.Cell(name=cell, chips=1, config={},
                                      traffic={}, metrics=[]),
                        seed=0, seconds=r["t_close"] - r["t_open"],
                        loop="open", t_open=r["t_open"],
                        t_close=r["t_close"])
    rec.model = run.architecture(config).ModelCosts(config["model"])
    rec.peak = r["peak"]
    rec.steps = [run.StepRecord(s["t0"], s["t1"], s["admitted"],
                                {int(k): v for k, v in s["served"].items()},
                                s["contexts"]) for s in r["steps"]]
    for rid, q in r["requests"].items():
        req = run.ReqRecord(rid=int(rid), due=r["t_open"],
                            prompt=[0] * q["prompt_len"], out_len=1)
        req.t_queued, req.t_admitted = q["t_queued"], q["t_admitted"]
        rec.requests[req.rid] = req
    return d, rec, d.pop("step_names")


CONFIGS = {"code": "starcoder2-3b", "longdoc": "minicpm-2b"}


@pytest.mark.parametrize("cell,metric,value", [
    # one request admitted: 0.321 ms from submit to admission
    ("code", "queue_wait_p90_ms", 0.32131),
    ("code", "logits_fetch_ms_per_step.serve", 1.2713573),
    # one active slot at a context of 1,162: attention is a sliver
    ("code", "decode_attn_roofline.serve", 0.29324628),
    ("longdoc", "logits_fetch_ms_per_step.batch", 0.8625667),
    ("longdoc", "decode_attn_roofline.batch", 3.8535162)])
def test_recorded_trace_reduces_to_the_new_metrics(cell, metric, value):
    trace, rec, names = _recorded(cell)
    rec.trace = enginetrace.summarize(trace, rec, names)
    got = run.metric_reader(metric)(rec)
    assert got == pytest.approx(value, rel=1e-6)
    assert 0 < got < 100


@pytest.mark.parametrize("cell", sorted(CONFIGS))
def test_recorded_decode_steps_wait_then_fetch_once(cell):
    """Every engine step of the recorded windows decodes, and holds one
    ``serve.wait`` and then one ``serve.fetch`` after its dispatch."""
    trace, _, _ = _recorded(cell)
    steps = [e for e in trace["host"] if e[2] == "engine.step"]
    assert len(steps) == 3
    for a, b, _ in steps:
        inside = [n for x, y, n in sorted(trace["host"]) if a <= x and y <= b
                  and n in ("serve.decode", "serve.wait", "serve.fetch")]
        assert inside == ["serve.decode", "serve.wait", "serve.fetch"]


def _without_spans(trace):
    return dict(trace, host=[e for e in trace["host"]
                             if not e[2].startswith("serve.")])


def _with_spans(trace):
    """``trace_code.json`` predates the engine's spans: give each of its
    steps an admit and a sample span, and the window a wait."""
    w0 = trace["host"][0][0]
    host = trace["host"] + [[a, b, f"serve.{n}"] for a, b, m in trace["host"]
                            if m == "engine.step" for n in ("admit", "sample")]
    return dict(trace, host=sorted(host + [[w0, w0 + 1, "serve.wait"]]))


@pytest.mark.parametrize("case", ["made_up", "trace_code", "code",
                                  "longdoc"])
def test_existing_metrics_unmoved_by_engine_spans(case):
    """The 13 accepted per-layer metrics read the same with the engine's
    spans in a trace as without them: on the made-up trace, on the
    recorded trace from before the spans, and on the recorded traces
    that carry them."""
    if case == "made_up":
        plain_trace, spanned_trace = _made_up(spans=False), _made_up()
        plain, spanned, names = _rec(), _rec(), NAMES
    elif case == "trace_code":
        plain_trace = json.loads((HERE / "data" / "trace_code.json")
                                 .read_text())
        spanned_trace = _with_spans(plain_trace)
        plain, spanned, names = _rec(), _rec(), NAMES
    else:
        spanned_trace, spanned, names = _recorded(case)
        plain_trace, plain = _without_spans(spanned_trace), _recorded(case)[1]
    plain.trace = devtrace.summarize(plain_trace, plain)
    spanned.trace = enginetrace.summarize(spanned_trace, spanned, names)
    assert spanned.trace["spans"]
    for key in ("window_s", "busy_s", "steps"):
        assert spanned.trace[key] == plain.trace[key]
    old, new = _existing(plain), _existing(spanned)
    assert len(old) == 13
    assert new == old
    if case == "made_up":
        assert None not in old.values()
