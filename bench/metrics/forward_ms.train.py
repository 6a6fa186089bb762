"""Device time of the forward pass per training step: operations in the
program's ``forward`` scope, engine launches left out (device trace, the
program's scopes; ``benchlib.program_trace``)."""


def read(r):
    t = r["trace"] or {}
    scopes = t.get("scope_s", {})
    n = t.get("span_counts", {}).get("step", 0)
    s = scopes.get("forward", 0.0) + scopes.get("jvp(forward)", 0.0)
    if not n or s <= 0:
        return None
    return 1e3 * s / n
