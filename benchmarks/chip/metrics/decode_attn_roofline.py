"""Share of its roofline that the decode step's attention reaches: the
least time its bytes (every active context's cached keys and values,
``ModelCosts.kv_bytes_per_token`` a token) and its operations need on
this chip, summed over the window's steps, over the device self time of
the ops under the ``paged_decode_attention`` scope in the decode
programs."""
import timing

SCOPE = "paged_decode_attention"


def read(run):
    dev = (run.trace or {}).get("scopes", {}).get(SCOPE)
    if not dev or not run.peak:
        return None
    steps = timing.window_steps(run)
    traced = run.trace["steps"]
    if len(steps) != len(traced):
        raise RuntimeError(f"{len(steps)} steps recorded, {len(traced)} "
                           f"traced")
    m, bound = run.model, 0.0
    for s, t in zip(steps, traced):
        if t["decode_s"] > 0 and s.contexts:
            ctx = sum(s.contexts)
            bound += max(m.kv_bytes_per_token * ctx
                         / run.peak["hbm_bytes_per_s"],
                         4 * m.L * m.nq * m.hd * ctx
                         / run.peak["bf16_flops"])
    return 100.0 * bound / dev
