"""Device idle time while the host reads a step's tokens and census back
(``serve.readback``), per decode step (device trace, the program's
spans; ``benchlib.program_trace``)."""


def read(r):
    t = r["trace"] or {}
    n = t.get("program_span_counts", {}).get("serve.step", 0)
    s = t.get("program_idle_s", {}).get("serve.readback")
    if not n or s is None:
        return None
    return 1e3 * s / n
