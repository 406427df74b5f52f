"""The serving engine's own instrumentation: ``serve.*`` host spans in a
profiler trace, their metadata only while the profiler records, the
queue and admission stamps on each request, and the named scopes that
the compiled decode step carries."""
import glob
import os
import re

import numpy as np
import pytest

import jax

from repro.configs import ARCHS, smoke_config
from repro.models import init_params
from repro.models.model import ModelRuntime
from repro.serve import PagedServeEngine, Request
from repro.serve import engine as engine_mod

CFG = smoke_config(ARCHS["starcoder2-3b"])
RT = ModelRuntime(dtype="float32", remat="none", attn_chunk=16)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _engine(params):
    return PagedServeEngine(params, CFG, RT, n_slots=2, max_len=64,
                            page_size=8)


def _requests(base, n=3):
    rng = np.random.default_rng(base)
    return [Request(rid=base + i, max_new_tokens=3,
                    prompt=rng.integers(0, CFG.vocab_size, 10 + i)
                    .astype(np.int32)) for i in range(n)]


def _host_spans(tdir):
    """(start, end, name, metadata) of every ``serve.*`` event."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out += [(e.start_ns, e.end_ns, e.name, dict(e.stats))
                    for e in line.events if e.name.startswith("serve.")]
    return sorted(out)


@pytest.fixture(scope="module")
def traced(params, tmp_path_factory):
    """Three requests on two slots, served under the profiler once every
    program has compiled, each step inside a ``step`` span."""
    eng = _engine(params)
    for r in _requests(100):
        eng.submit(r)
    eng.run()
    tdir = str(tmp_path_factory.mktemp("trace"))
    reqs = _requests(0)
    steps = []
    with jax.profiler.trace(tdir):
        for r in reqs:
            eng.submit(r)
        while eng.queue or any(s is not None for s in eng.slots):
            with jax.profiler.TraceAnnotation("step"):
                eng.step()
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                        recursive=True)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            steps += [(e.start_ns, e.end_ns) for e in line.events
                      if e.name == "step"]
    return sorted(steps), _host_spans(tdir), reqs


def _inside(span, outer):
    return outer[0] <= span[0] and span[1] <= outer[1]


def test_each_decode_step_waits_then_fetches_then_samples(traced):
    steps, spans, _ = traced
    assert steps
    for step in steps:
        names = [s[2] for s in spans if _inside(s, step)
                 and s[2] in ("serve.decode", "serve.wait", "serve.fetch",
                              "serve.sample")]
        assert names == ["serve.decode", "serve.wait", "serve.fetch",
                         "serve.sample"]
        assert [s[2] for s in spans if _inside(s, step)].count(
            "serve.admit") == 1


def test_admission_holds_prefill_scatter_and_splice(traced):
    steps, spans, reqs = traced
    admits = [s for s in spans if s[2] == "serve.admit"]
    prefills = [s for s in spans if s[2] == "serve.prefill"]
    assert len(prefills) == len(reqs)       # one admission a request
    for p in prefills:
        admit = next(a for a in admits if _inside(p, a))
        inner = [s[2] for s in spans if _inside(s, admit)
                 and s[0] >= p[0] and s[2] != "serve.admit"]
        assert inner[:4] == ["serve.prefill", "serve.prefill_fetch",
                             "serve.scatter", "serve.splice"]
    rids = sorted(int(str(p[3]["rids"])) for p in prefills)
    assert rids == [r.rid for r in reqs]
    assert all(int(p[3]["bucket"]) >= 10 for p in prefills)
    # every request retires: its pages go back under serve.release
    assert sum(s[2] == "serve.release" for s in spans) == len(reqs)


def test_decode_metadata_names_the_active_slots(traced):
    _, spans, _ = traced
    slots = {str(s[3]["slots"]) for s in spans if s[2] == "serve.decode"}
    assert slots <= {"0", "1", "0 1"} and "0 1" in slots


class _Recorder:
    """Stands in for ``TraceAnnotation``: records each span's metadata."""

    enabled = False
    calls = []

    def __init__(self, name, **meta):
        self.calls.append((name, meta))

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("enabled", [False, True])
def test_metadata_built_only_while_the_profiler_records(params, monkeypatch,
                                                         enabled):
    eng = _engine(params)
    monkeypatch.setattr(engine_mod, "TraceAnnotation", _Recorder)
    monkeypatch.setattr(_Recorder, "enabled", enabled)
    monkeypatch.setattr(_Recorder, "calls", [])
    for r in _requests(7, n=2):
        eng.submit(r)
    eng.step()
    meta = {n: m for n, m in _Recorder.calls}
    assert {"serve.admit", "serve.prefill", "serve.decode",
            "serve.wait", "serve.fetch", "serve.sample"} <= set(meta)
    if enabled:
        assert meta["serve.decode"] == {"slots": "0 1"}
        assert meta["serve.prefill"]["rids"] in ("7", "8")
    else:
        assert all(m == {} for _, m in _Recorder.calls)


def test_queue_and_admission_stamps(params):
    eng = _engine(params)
    reqs = _requests(20, n=4)             # two slots: two wait a turn
    for r in reqs:
        eng.submit(r)
    assert all(np.isnan(r.t_admitted) for r in reqs)
    eng.run()
    for r in reqs:
        assert r.t_queued <= r.t_admitted
    first = sorted(r.t_admitted for r in reqs)
    assert first[0] < first[2]            # the later two waited for slots


def test_rejected_request_is_never_queued(params):
    eng = _engine(params)
    big = Request(rid=1, prompt=np.ones(70, np.int32), max_new_tokens=3)
    eng.submit(big)
    assert eng.rejected == [big]
    assert np.isnan(big.t_queued) and np.isnan(big.t_admitted)


def test_decode_step_carries_its_scopes(params):
    """The compiled decode step names the paged attention kernel, the
    MLP and the KV page write in its instructions' ``op_name``."""
    eng = _engine(params)
    hlo = eng._step.lower(eng.params, eng.cache,
                          jax.numpy.asarray(eng.last_tokens)) \
        .compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in ("paged_decode_attention", "mlp", "kv_write"):
        assert any(n.startswith("jit(_step_fn)/") and f"/{scope}/" in n
                   for n in names), scope
