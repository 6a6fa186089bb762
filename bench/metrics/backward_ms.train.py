"""Device time of the backward pass per training step: operations under
``transpose(jvp(forward))``, the forward that remat recomputes there
included, engine launches left out (device trace, the program's scopes;
``benchlib.program_trace``)."""


def read(r):
    t = r["trace"] or {}
    s = t.get("scope_s", {}).get("transpose(jvp(forward))", 0.0)
    n = t.get("span_counts", {}).get("step", 0)
    if not n or s <= 0:
        return None
    return 1e3 * s / n
