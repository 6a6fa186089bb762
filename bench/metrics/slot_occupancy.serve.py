"""Tokens produced over slot-steps run: engine steps (prefill and decode)
times slots. Slots held empty to the end of a wave lower it."""


def read(r):
    c = r["counters"]
    return 100.0 * sum(c["out_lens"]) / (c["engine_steps"] * c["slots"])
