"""Device time of the MoE layers per engine step (prefill or decode): the
program's ``moe_router``, ``moe_experts`` and ``moe_shared`` scopes, the
routed experts' grouped-matmul kernels and the layer loop's slices of
their weights counted in ``moe_experts`` (device trace, the program's
scopes; ``benchlib.moe_trace``)."""

SCOPES = ("moe_router", "moe_experts", "moe_shared")


def read(r):
    t = r["trace"] or {}
    c = t.get("span_counts", {})
    n = c.get("prefill", 0) + c.get("decode", 0)
    scopes = t.get("moe_scope_s", {})
    s = sum(scopes.get(k, 0.0) for k in SCOPES)
    if not n or s <= 0:
        return None
    return 1e3 * s / n
