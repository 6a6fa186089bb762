"""Share of the slot-steps run that gave a request a token, from the
runtime's counters (``ServeMetrics.live_slot_steps / slot_steps``; host
clock)."""


def read(r):
    c = r["counters"]
    if not c.get("slot_steps"):
        return None
    return 100.0 * c["live_slot_steps"] / c["slot_steps"]
