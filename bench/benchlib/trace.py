"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy time, device time by operation name, idle gaps
named by the harness's own host spans, and the time of the reduction
engine's launches.

Busy time is the union of the intervals in which an operation ran on a
device, inside the harness's ``window`` span; idle is the rest of that
window. Each idle gap is named by the host span (``SPANS``) that overlaps
it most, or ``other``.

A Mosaic launch is named in the trace after the jitted function around
it, not after its kernel. The trace file also holds the HLO of every
program that ran, and each launch's compiled kernel names its function
there; the engine's launches are those whose kernel is in
``ENGINE_KERNELS``. Any other Mosaic kernel is kept apart.
"""

from __future__ import annotations

import base64
import glob
import json
import os
import re
import shutil

SPANS = ("data", "step", "sync", "admit", "prefill", "decode", "idle_wait")
WINDOW = "window"
DEVICE_LINE = "XLA Ops"
MOSAIC_OP = "custom_call_target=\"tpu_custom_call\""
# The reduction engine's Pallas kernels, by the name of the kernel
# function (repro.kernels.mma_reduce.kernel and repro.kernels.scan).
ENGINE_KERNELS = ("tile_partials_kernel", "fused_accumulate_kernel",
                  "fused_moments_kernel", "fused_kahan_kernel",
                  "segmented_gather_kernel", "parts_accumulate_kernel",
                  "scan_kernel")
_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
             "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8}
_SHAPE = re.compile(r"\b(bf16|f16|f32|f64|s8|u8|s32|u32|s64|u64|pred)"
                    r"\[([0-9,]*)\]")


def op_name(text: str) -> str:
    """The HLO instruction's name, from the event's text
    (``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``)."""
    head = text.split(" = ", 1)[0]
    return head.lstrip("%")


def _opcode(text: str):
    rest = text.split(" = ", 1)[-1]
    return re.search(r"\s([a-z][a-z0-9-]*)\(", rest), rest


def operand_bytes(text: str) -> int:
    """Bytes of the operands an HLO instruction names, from its text."""
    m, rest = _opcode(text)
    if not m:
        return 0
    depth, end = 1, len(rest)
    for i in range(m.end(), len(rest)):
        depth += {"(": 1, ")": -1}.get(rest[i], 0)
        if depth == 0:
            end = i
            break
    total = 0
    for dt, dims in _SHAPE.findall(rest[m.end():end]):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _ITEMSIZE[dt]
    return total


def _varint(b: bytes, i: int):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


_CUSTOM_CALL = b"\x12\x0bcustom-call"   # HloInstructionProto.opcode


def _instruction_fields(raw: bytes, i: int) -> dict:
    """The fields of the serialized ``HloInstructionProto`` that starts at
    ``raw[i]``: its fields come in ascending number, so the first number
    that does not ascend belongs to the message around it."""
    out, last = {}, 0
    while i < len(raw):
        tag, j = _varint(raw, i)
        field, wire = tag >> 3, tag & 7
        if field <= last or wire not in (0, 1, 2, 5):
            break
        if wire == 0:
            _, j = _varint(raw, j)
        elif wire == 1:
            j += 8
        elif wire == 5:
            j += 4
        else:
            n, j = _varint(raw, j)
            out[field] = raw[j:j + n]
            j += n
        last, i = field, j
    return out


def _kernel_function(body_b64: str) -> str:
    """The kernel function a compiled Mosaic launch runs: the name among
    the strings of its serialized module."""
    words = base64.b64decode(body_b64).split(b"\x00")
    names = [w.decode("ascii", "replace") for w in words
             if re.fullmatch(rb"[A-Za-z_][A-Za-z0-9_]*kernel", w)]
    for n in names:
        if n in ENGINE_KERNELS:
            return n
    return names[0] if names else "?"


def mosaic_kernels(raw: bytes) -> dict:
    """``{instruction name: {kernel function, ...}}`` for every Mosaic
    launch of the HLO modules that a trace file holds. Names repeat across
    programs, so a name may map to more than one kernel."""
    out: dict = {}
    p = raw.find(_CUSTOM_CALL)
    while p >= 0:
        for n in range(1, 128):     # the name field: 0x0a, length, name
            if p - n - 2 >= 0 and raw[p - n - 2] == 0x0A \
                    and raw[p - n - 1] == n:
                f = _instruction_fields(raw, p - n - 2)
                if f.get(28) == b"tpu_custom_call" and 43 in f:
                    cfg = json.loads(f[43])
                    body = cfg["custom_call_config"]["body"]
                    out.setdefault(f[1].decode(), set()).add(
                        _kernel_function(body))
                break
        p = raw.find(_CUSTOM_CALL, p + 1)
    return out


def is_engine(kernels: set) -> bool:
    return bool(kernels) and kernels <= set(ENGINE_KERNELS)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """(text, start, end, self) for nested events: an operation's own
    time is its duration less that of the operations it contains."""
    evs = sorted(events, key=lambda x: (x[1], -x[2]))
    out, stack = [], []
    for text, s, e in evs:
        while stack and stack[-1][2] <= s:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= e - s
        stack.append([text, s, e, e - s])
    out.extend(tuple(x) for x in stack)
    return out


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def device_planes(pd) -> list:
    return [p for p in pd.planes if p.name.startswith("/device:TPU:")]


def reduce_trace(pd, kernels: dict) -> dict:
    """The reduced trace. Times in seconds, per chip on average; ``ops`` is
    each operation's own time by name, ``custom_calls`` the engine's
    launches with the bytes of their operands, and ``other_kernels`` the
    time of every other Mosaic launch. ``kernels`` names each launch's
    kernel functions (``mosaic_kernels``)."""
    spans, window = [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name in SPANS:
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    if window is None:
        raise ValueError("trace holds no harness 'window' span")
    w0, w1 = window
    spans = [x for x in spans if x[0] >= w0 and x[1] <= w1]
    counts: dict = {}
    for _, _, n in spans:
        counts[n] = counts.get(n, 0) + 1
    devs = device_planes(pd)
    if not devs:
        raise ValueError("trace holds no TPU device plane")
    ops: dict = {}
    calls: dict = {}
    other: dict = {}
    busy = engine = 0.0
    gaps: dict = {}
    for plane in devs:
        evs = []
        for line in plane.lines:
            if line.name != DEVICE_LINE:
                continue
            for ev in line.events:
                s = max(ev.start_ns, w0)
                e = min(ev.start_ns + ev.duration_ns, w1)
                if e > s:
                    evs.append((ev.name, s, e))
        for text, s, e, own in _self_times(evs):
            d = own * 1e-9
            name = op_name(text)
            ops[name] = ops.get(name, 0.0) + d
            if MOSAIC_OP not in text:
                continue
            if is_engine(kernels.get(name, set())):
                engine += d
                c = calls.setdefault(name, {"s": 0.0, "n": 0,
                                            "in_bytes": operand_bytes(text)})
                c["s"] += d
                c["n"] += 1
            else:
                other[name] = other.get(name, 0.0) + d
        merged = _union([(s, e) for _, s, e in evs])
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for se in merged for x in se] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            label = _label(spans, a, b)
            gaps[label] = gaps.get(label, 0.0) + (b - a) * 1e-9
    nd = len(devs)
    for c in calls.values():
        c["s"] /= nd
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy / nd,
        "chips": nd,
        "span_counts": counts,
        "ops": {k: v / nd for k, v in ops.items()},
        "engine_s": engine / nd,
        "custom_calls": calls,
        "other_kernels": {k: v / nd for k, v in other.items()},
        "idle_by_span": {k: v / nd for k, v in gaps.items()},
    }


def _label(spans, a: int, b: int) -> str:
    best, name = 0, "other"
    for s, e, n in spans:
        o = min(b, e) - max(a, s)
        if o > best:
            best, name = o, n
    return name


def breakdown(red: dict) -> dict:
    """The result line's ``breakdown``: the ten device operations that took
    most time, and idle time by what the host was doing."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(red["idle_by_span"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}


class Tracer:
    """Records a profiler trace into ``out_dir`` and reduces the stretch
    that the harness's ``window`` span marks in it. A serving cell starts
    the profiler before its window and stops it after, so that neither
    stalls a request, and marks a stretch inside (``arm``/``tick``)."""

    def __init__(self, out_dir: str):
        self.out_dir = str(out_dir)
        self._span = None
        self._mark = None
        self._marked = False

    def start(self):
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        # host spans and device operations; no trace of every Python call
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)

    def stop(self):
        import jax

        self.close()
        jax.profiler.stop_trace()

    def open(self):
        from jax.profiler import TraceAnnotation

        self._span = TraceAnnotation(WINDOW)
        self._span.__enter__()

    def close(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
            self._marked = True

    def __enter__(self):
        self.start()
        self.open()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def arm(self, seconds: float, at: float):
        """Mark ``at`` to ``at + seconds`` as the window, as ``tick`` is
        told the time since the run's own window began."""
        self._mark = (at, at + seconds)

    def tick(self, elapsed: float):
        if self._mark is None or self._marked:
            return
        if self._span is None and elapsed >= self._mark[0]:
            self.open()
        elif self._span is not None and elapsed >= self._mark[1]:
            self.close()

    def reduce(self) -> dict:
        paths = glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError(f"no trace written under {self.out_dir}")
        with open(paths[0], "rb") as f:
            kernels = mosaic_kernels(f.read())
        red = reduce_trace(load(paths[0]), kernels)
        shutil.rmtree(self.out_dir, ignore_errors=True)
        return red
