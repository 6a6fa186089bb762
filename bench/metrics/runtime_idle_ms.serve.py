"""Device idle time inside a wave (``serve.wave``) but in neither a
step's dispatch nor its read-back: the runtime's own host work between
steps, per decode step (device trace, the program's spans;
``benchlib.program_trace``)."""

ENGINE_SPANS = ("serve.dispatch", "serve.readback")


def read(r):
    t = r["trace"] or {}
    n = t.get("program_span_counts", {}).get("serve.step", 0)
    idle = t.get("program_idle_s")
    if not n or idle is None:
        return None
    s = sum(v for k, v in idle.items()
            if k.startswith("serve.") and k not in ENGINE_SPANS)
    return 1e3 * s / n
