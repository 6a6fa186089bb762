"""Device time of multi-head latent attention per engine step (prefill or
decode): the program's ``mla`` scope (device trace, the program's scopes;
``benchlib.moe_trace``)."""


def read(r):
    t = r["trace"] or {}
    c = t.get("span_counts", {})
    n = c.get("prefill", 0) + c.get("decode", 0)
    s = t.get("moe_scope_s", {}).get("mla", 0.0)
    if not n or s <= 0:
        return None
    return 1e3 * s / n
