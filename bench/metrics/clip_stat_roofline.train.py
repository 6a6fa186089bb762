"""The optimizer's one clip-statistic launch against its roofline. The
launch is the engine kernel whose operands are the bf16 gradient tree
(device trace); the statistic needs to read the tree once, 2 bytes and 2
FLOP per element, so its least time is the tree's bytes over the HBM
bandwidth (the FLOP bound is 240 times smaller). The share is that time
over the launch's device time per step."""

from benchlib import flops


def read(r):
    t = r["trace"]
    steps = (t or {}).get("span_counts", {}).get("step", 0)
    if not steps:
        return None
    tree = 2.0 * flops.param_count(r["model"])
    launch = sum(c["s"] for c in t["custom_calls"].values()
                 if c["in_bytes"] >= 0.9 * tree)
    if launch <= 0:
        return None
    p = r["peaks"]
    t_min = max(tree / p.hbm_bytes_per_s, tree / p.bf16_flops)
    return 100.0 * t_min / (launch / steps)
