"""Share of its roofline that the held experts' grouped GEMMs reach in
the decode programs: the least time of their work on this chip, the
weights of every held expert that a step's tokens hit (read once a
layer and step) and the rows' inputs and outputs, or two operations a
multiply-add of every routed row's three products, summed over the
window, over the device self time of the ops under the ``moe_gemm``
scope in the decode programs.

The work comes from the engine's counters (``EngineStats.expert_rows``,
``experts_hit``, summed over layers and decode steps) and the scope's
time from the decode program's compiled HLO. Until the harness keeps
both in its record (``run.expert_rows``, ``run.experts_hit``,
``run.trace["scopes"]``) it reads nothing and returns None."""

SCOPE = "moe_gemm"


def read(run):
    dev = (run.trace or {}).get("scopes", {}).get(SCOPE)
    rows = getattr(run, "expert_rows", None)
    hit = getattr(run, "experts_hit", None)
    if not dev or rows is None or hit is None or not run.peak:
        return None
    m = run.model
    flops = 2 * m.expert_params * rows
    bytes_ = m.b * (hit * m.expert_params + 2 * m.d * rows)
    bound = max(flops / run.peak["bf16_flops"],
                bytes_ / run.peak["hbm_bytes_per_s"])
    return 100.0 * bound / dev
