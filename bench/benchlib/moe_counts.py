"""Operations and bytes of the routed experts of a MoE configuration with
held experts (``model.moe``: ``n_experts`` router outputs, ``held_count``
of them held here), counted from its published sizes and the program's
counters, never from the program itself.

A decode call that computes ``pairs`` routed (token, expert) pairs on the
held experts, summed over the MoE layers, needs:

- FLOPs: each pair is one SwiGLU of ``d_ff_expert``: three matrix
  products of d x f, 2 * 3 * d * f FLOPs;
- bytes: every held expert's three weight matrices read once per MoE
  layer (a held expert that no token chose still loads in a grouped
  matmul over fixed groups), and each pair's token row read in and its
  output row written, ``d`` values each, in the weights' dtype.
"""

from __future__ import annotations

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def moe_layers(m: dict) -> int:
    return m["n_layers"] - m.get("first_k_dense", 0)


def held(m: dict) -> int:
    e = m["moe"]
    return e.get("held_count") or e["n_experts"]


def expert_flops(m: dict, pairs: float) -> float:
    return 6.0 * m["d_model"] * m["moe"]["d_ff_expert"] * pairs


def expert_bytes(m: dict, pairs: float) -> float:
    """Bytes of one decode call that computes ``pairs`` pairs."""
    size = _ITEMSIZE[m.get("dtype", "bfloat16")]
    d, f = m["d_model"], m["moe"]["d_ff_expert"]
    weights = moe_layers(m) * held(m) * 3 * d * f * size
    return weights + 2.0 * pairs * d * size


def expert_time_s(m: dict, pairs: float, peaks) -> float:
    """The least time of one decode call's routed experts on a chip of
    ``peaks``: its FLOPs at the bf16 peak or its bytes at the HBM
    bandwidth, whichever takes longer."""
    return max(expert_flops(m, pairs) / peaks.bf16_flops,
               expert_bytes(m, pairs) / peaks.hbm_bytes_per_s)
