"""Device time of the reduction engine's kernels per engine step, prefill
or decode (device trace)."""


def read(r):
    t = r["trace"]
    c = (t or {}).get("span_counts", {})
    n = c.get("prefill", 0) + c.get("decode", 0)
    if not n or t["engine_s"] <= 0:
        return None
    return 1e3 * t["engine_s"] / n
