"""Device time of the reduction engine's kernels per training step
(device trace; the kernels are matched by the names in benchlib.trace)."""


def read(r):
    t = r["trace"]
    n = (t or {}).get("span_counts", {}).get("step", 0)
    if not n or t["engine_s"] <= 0:
        return None
    return 1e3 * t["engine_s"] / n
