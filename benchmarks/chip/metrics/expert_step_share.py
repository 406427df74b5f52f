"""Share of the decode programs' device time spent in the expert layer:
the self time of the ops under the ``moe_gemm`` (the held experts'
grouped GEMMs) and ``moe_route`` (router, top-k, sort and combine)
scopes, over the device time of the decode programs in the window.

Until the harness keeps the decode program's scopes
(``run.trace["scopes"]``, with ``moe_route`` among the scopes it
knows) it reads nothing and returns None."""

SCOPES = ("moe_gemm", "moe_route")


def read(run):
    scopes = (run.trace or {}).get("scopes", {})
    if not any(s in scopes for s in SCOPES):
        return None
    decode = sum(s["decode_s"] for s in run.trace["steps"])
    if not decode:
        return None
    return 100.0 * sum(scopes.get(s, 0.0) for s in SCOPES) / decode
