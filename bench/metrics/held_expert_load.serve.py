"""Routed (token, expert) pairs per held expert, MoE layer and decode
call: the engine's counters (``GuardedEngine.moe_held_pairs`` over
``moe_decode_calls``) over the held experts and MoE layers of the
configuration."""

from benchlib import moe_counts


def read(r):
    c, m = r["counters"], r["model"]
    calls = c.get("moe_decode_calls", 0)
    if not calls or "moe" not in m:
        return None
    return c["moe_held_pairs"] / (calls * moe_counts.held(m)
                                  * moe_counts.moe_layers(m))
