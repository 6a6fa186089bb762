"""The whole step's share of the chips' bf16 peak: the FLOPs the forward
and backward passes require per token (bench flops), times the window's
tokens per second, over chips times peak."""

from benchlib import flops


def read(r):
    c = r["counters"]
    per_tok = flops.train_flops_per_token(r["model"], c["seq"])
    rate = c["tokens"] / c["window_s"]
    return 100.0 * per_tok * rate / (r["cell"]["chips"]
                                     * r["peaks"].bf16_flops)
