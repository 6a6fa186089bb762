"""The routed experts of a decode call against their roofline: the least
time of the call's expert FLOPs at the bf16 peak or its bytes (the held
experts' weights once per MoE layer, each routed token row in and out) at
the HBM bandwidth (``benchlib.moe_counts``, from the configuration's
published sizes and the engine's routed-pair counters), over the device
time of the ``moe_experts`` scope per traced decode call, the layer loop's
slices of the held experts' weights included (device trace,
``benchlib.moe_trace``)."""

from benchlib import moe_counts


def read(r):
    t, c = r["trace"] or {}, r["counters"]
    n = t.get("span_counts", {}).get("decode", 0)
    s = t.get("moe_scope_s_decode", {}).get("moe_experts", 0.0)
    calls = c.get("moe_decode_calls", 0)
    if not n or s <= 0 or not calls:
        return None
    pairs = c["moe_held_pairs"] / calls
    t_min = moe_counts.expert_time_s(r["model"], pairs, r["peaks"])
    return 100.0 * t_min / (s / n)
