"""The FLOPs of every prompt and every decoded token of the requests
served in the window (bench flops), over the window times the chips' bf16
peak."""

from benchlib import flops


def read(r):
    c = r["counters"]
    m, p = r["model"], c["prompt_len"]
    total = sum(flops.prefill_flops(m, p) + flops.decode_flops(m, p, n - 1)
                for n in c["out_lens"])
    return 100.0 * total / (c["window_s"] * r["cell"]["chips"]
                            * r["peaks"].bf16_flops)
