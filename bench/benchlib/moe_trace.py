"""Device time of the MLA and MoE scopes in a profiler trace, beside what
``benchlib.trace`` and ``benchlib.program_trace`` read.

The serving steps carry ``jax.named_scope`` names ``mla``, ``moe_router``,
``moe_experts`` and ``moe_shared`` in each instruction's op name
(``models/mla.py``, ``models/moe.py``). An operation's scope is the
innermost of ``SCOPES`` in its op name. The grouped matmuls of the routed
experts are Mosaic kernels that the TPU compiler names ``ragged-dot-*``
and gives no op name of the program's; they count as ``moe_experts``.
Operations are looked up, as in ``benchlib.program_trace``, in the
program whose ``XLA Modules`` event encloses them. The layer loop's slice
of the held experts' stacked weights carries no scope of the program (its
op name is the loop's own); the grouped matmuls cannot read the stack in
place, so each layer's slice is materialized, and on the TPU it is where
the weights are read from HBM. An operation whose output has the shape of
that slice (``held_weight_shapes``) counts as ``moe_experts``.

``reduce_scopes`` gives, in seconds per chip inside the harness's
``window`` span, ``moe_scope_s`` (every step) and ``moe_scope_s_decode``
(operations that start inside the harness's ``decode`` spans: a decode
call reads its tokens back before it returns, so its operations run
inside its span).
"""

from __future__ import annotations

import bisect
import glob
import os
import shutil

from benchlib import moe_counts
from benchlib.program_trace import (MODULE_LINE, ProgramTracer, _base,
                                    hlo_op_names)
from benchlib.trace import (DEVICE_LINE, WINDOW, _self_times, device_planes,
                            load, mosaic_kernels, op_name, reduce_trace)

SCOPES = ("mla", "moe_router", "moe_experts", "moe_shared")
KERNEL_SCOPE = {"ragged-dot": "moe_experts"}
DECODE = "decode"


def scope_of(name: str, instruction: str) -> str:
    """The innermost of ``SCOPES`` in an op name ("" for none); a grouped
    matmul kernel by its instruction name."""
    for prefix, scope in KERNEL_SCOPE.items():
        if instruction.startswith(prefix):
            return scope
    scope = ""
    for part in name.split(";")[0].split("/"):
        if _base(part) in SCOPES:
            scope = _base(part)
    return scope


def held_weight_shapes(m: dict) -> tuple:
    """Output shapes, as HLO text, of one MoE layer's slice of the held
    experts' stacked weights (``model`` dict ``m``): gate and up (held, d,
    f), down (held, f, d)."""
    h, d, f = moe_counts.held(m), m["d_model"], m["moe"]["d_ff_expert"]
    dt = {"bfloat16": "bf16", "float32": "f32"}[m.get("dtype", "bfloat16")]
    return (f"{dt}[{h},{d},{f}]", f"{dt}[{h},{f},{d}]")


def reduce_scopes(pd, op_names: dict, weight_shapes: tuple = ()) -> dict:
    window, decode = None, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name == DECODE:
                    decode.append((ev.start_ns, ev.start_ns + ev.duration_ns))
    if window is None:
        raise ValueError("trace holds no harness 'window' span")
    w0, w1 = window
    decode.sort()
    starts = [s for s, _ in decode]
    devs = device_planes(pd)
    if not devs:
        raise ValueError("trace holds no TPU device plane")
    every: dict = {}
    in_decode: dict = {}
    for plane in devs:
        evs, modules = [], []
        for line in plane.lines:
            if line.name == MODULE_LINE:
                modules.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name) for ev in line.events)
            elif line.name == DEVICE_LINE:
                for ev in line.events:
                    s = max(ev.start_ns, w0)
                    e = min(ev.start_ns + ev.duration_ns, w1)
                    if e > s:
                        evs.append((ev.name, s, e))
        modules.sort()
        m = 0
        for text, s, e, own in sorted(_self_times(evs), key=lambda x: x[1]):
            while m + 1 < len(modules) and modules[m][1] <= s:
                m += 1
            name = op_name(text)
            prog = modules[m][2] if modules and modules[m][0] <= s else None
            scope = scope_of(op_names.get(prog, {}).get(name, ""), name)
            if not scope and text.split(" = ", 1)[-1].startswith(
                    weight_shapes):
                scope = "moe_experts"
            if not scope:
                continue
            every[scope] = every.get(scope, 0.0) + own * 1e-9
            k = bisect.bisect_right(starts, s) - 1
            if k >= 0 and decode[k][1] > s:
                in_decode[scope] = in_decode.get(scope, 0.0) + own * 1e-9
    nd = len(devs)
    return {"moe_scope_s": {k: v / nd for k, v in every.items()},
            "moe_scope_s_decode": {k: v / nd for k, v in in_decode.items()}}


class MoETracer(ProgramTracer):
    """``ProgramTracer`` whose reduction adds ``reduce_scopes``' keys for
    the configuration ``model`` (a ``model`` dict)."""

    def __init__(self, out_dir: str, model: dict):
        super().__init__(out_dir)
        self.weight_shapes = held_weight_shapes(model)

    def reduce(self) -> dict:
        paths = glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError(f"no trace written under {self.out_dir}")
        with open(paths[0], "rb") as f:
            raw = f.read()
        kernels = mosaic_kernels(raw)
        pd = load(paths[0])
        names = hlo_op_names(raw)
        red = reduce_trace(pd, kernels)
        from benchlib.program_trace import reduce_program

        red.update(reduce_program(pd, names, kernels))
        red.update(reduce_scopes(pd, names, self.weight_shapes))
        shutil.rmtree(self.out_dir, ignore_errors=True)
        return red
