"""Device time of the optimizer per training step: the program's
``optimizer`` scope (the AdamW update and the guard's bit-blend keep)
less the engine's launches, which ``engine_ms.train`` counts (device
trace, the program's scopes; ``benchlib.program_trace``)."""


def read(r):
    t = r["trace"] or {}
    s = t.get("scope_s", {}).get("optimizer", 0.0)
    n = t.get("span_counts", {}).get("step", 0)
    if not n or s <= 0:
        return None
    return 1e3 * s / n
