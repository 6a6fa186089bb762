"""The program's own marks in a profiler trace, beside what
``benchlib.trace`` reads from the harness's marks.

- Host spans: the serving path opens spans named ``serve.*``
  (``repro.runtime.metrics.span``). Their counts and time inside the
  harness's ``window`` span, and every idle gap of the device cut into
  pieces, each named by the innermost ``serve.*`` span the host was in
  (``outside`` where it was in none).
- Device scopes: the compiled steps carry ``jax.named_scope`` names in the
  ``op_name`` of each instruction's metadata (``jit(step)/model/while/
  body/kv_cache/dynamic_slice``). The trace file holds the HLO of every
  program that ran; an operation is looked up in the program whose
  ``XLA Modules`` event encloses it, since instruction names repeat
  across programs. Its scope is the innermost of ``SCOPES`` in its op
  name, written as the component where that scope first appears: the
  backward is ``transpose(jvp(forward))``, and the forward that remat
  recomputes inside it (``transpose(jvp(forward))/jvp(forward)/...``)
  stays in the backward.

``reduce_program`` gives, in seconds per chip: ``program_span_counts``
and ``program_span_s``; ``program_idle_s``; ``scope_s``, the device self
time by scope ("" for none), the engine's launches left out (``engine_s``
holds them); ``scope_ops``, the three operations of each scope with the
most self time; and ``copy_s``, the self time of ``copy`` instructions
outside the ``kv_cache`` scope by the shape they copy.
"""

from __future__ import annotations

import glob
import os
import re
import shutil

from benchlib.trace import (DEVICE_LINE, MOSAIC_OP, WINDOW, Tracer, _SHAPE,
                            _opcode, _self_times, _union, device_planes,
                            is_engine, load, mosaic_kernels, op_name,
                            reduce_trace)

PREFIX = "serve."
OUTSIDE = "outside"
MODULE_LINE = "XLA Modules"
SCOPES = ("forward", "optimizer", "model", "kv_cache", "census")
_WRAPPED = re.compile(r"[A-Za-z_][\w-]*\((.*)\)")


# ---- the HLO that the trace file carries ----------------------------------

def _varint(b, i: int):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b):
    """(field number, value) of a serialized message; a length-delimited
    value is a memoryview into ``b``."""
    i = 0
    while i < len(b):
        tag, i = _varint(b, i)
        field, wire = tag >> 3, tag & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 5:
            v, i = None, i + 4
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        else:
            raise ValueError(f"wire type {wire} in a trace file")
        yield field, v


def _sub(b, field: int):
    return [v for f, v in _fields(b) if f == field]


def _module_op_names(hlo_proto) -> dict:
    """``{instruction name: op_name}`` of a serialized ``HloProto``."""
    out = {}
    for module in _sub(hlo_proto, 1):                  # hlo_module
        for comp in _sub(module, 3):                   # computations
            for ins in _sub(comp, 2):                  # instructions
                name = meta = None
                for f, v in _fields(ins):
                    if f == 1:
                        name = bytes(v).decode()
                    elif f == 7:
                        meta = v
                if name is None or meta is None:
                    continue
                for f, v in _fields(meta):
                    if f == 2:                          # OpMetadata.op_name
                        out[name] = bytes(v).decode()
    return out


def hlo_op_names(raw: bytes) -> dict:
    """``{program: {instruction name: op_name}}`` from a trace file; a
    program is named as its ``XLA Modules`` events are
    (``jit_step(<program id>)``)."""
    out = {}
    for f, plane in _fields(memoryview(raw)):
        if f != 1:                                      # XSpace.planes
            continue
        name, events, stat_names = None, [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = bytes(v).decode()
            elif g == 4:
                events.append(_sub(v, 2)[0])            # map entry value
            elif g == 5:
                entry = _sub(v, 2)[0]
                sid = _sub(entry, 1)
                sname = _sub(entry, 2)
                if sid and sname:
                    stat_names[sid[0]] = bytes(sname[0]).decode()
        if name != "/host:metadata":
            continue
        for ev in events:
            prog = _sub(ev, 2)
            for stat in _sub(ev, 5):
                st = dict(_fields(stat))
                if stat_names.get(st.get(1)) == "Hlo Proto" and 6 in st:
                    out[bytes(prog[0]).decode()] = _module_op_names(st[6])
    return out


# ---- scopes ---------------------------------------------------------------

def _base(part: str) -> str:
    """A name stack component without its transforms:
    ``transpose(jvp(forward))`` -> ``forward``."""
    m = _WRAPPED.fullmatch(part)
    while m:
        part = m.group(1)
        m = _WRAPPED.fullmatch(part)
    return part


def scope_of(name: str) -> str:
    """The program scope of an op name ("" for none). Fused instructions
    join their ops' names with ``;``; the first one names the scope."""
    scope, base = "", None
    for part in name.split(";")[0].split("/"):
        b = _base(part)
        if b in SCOPES and b != base:
            scope, base = part, b
    return scope


# ---- host spans -----------------------------------------------------------

def _innermost_pieces(spans):
    """Time cut at every span edge, each piece named by the innermost span
    over it (spans of one thread nest): sorted ``(start, end, name)``."""
    edges = sorted({x for s, e, _ in spans for x in (s, e)})
    starts = sorted(spans)
    out, active, j = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while j < len(starts) and starts[j][0] <= a:
            active.append(starts[j])
            j += 1
        active = [x for x in active if x[1] > a]
        if active:
            inner = max(active, key=lambda x: (x[0], -x[1]))
            out.append((a, b, inner[2]))
    return out


def _name_gaps(gaps, pieces, acc: dict) -> None:
    """Add each gap's time to ``acc`` under the pieces it overlaps, the
    rest under ``OUTSIDE``; both lists sorted and each disjoint."""
    j = 0
    for a, b in gaps:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        covered = 0
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            o = min(b, pieces[k][1]) - max(a, pieces[k][0])
            if o > 0:
                acc[pieces[k][2]] = acc.get(pieces[k][2], 0.0) + o * 1e-9
                covered += o
            k += 1
        if b - a > covered:
            acc[OUTSIDE] = acc.get(OUTSIDE, 0.0) + (b - a - covered) * 1e-9


# ---- the reduction --------------------------------------------------------

def reduce_program(pd, op_names: dict, kernels: dict) -> dict:
    """The program's spans and scopes in the harness's window. ``op_names``
    is ``hlo_op_names`` of the trace file, ``kernels`` its
    ``mosaic_kernels``."""
    window, spans = None, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name.startswith(PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    if window is None:
        raise ValueError("trace holds no harness 'window' span")
    w0, w1 = window
    counts: dict = {}
    span_s: dict = {}
    for s, e, n in spans:
        if s >= w0 and e <= w1:
            counts[n] = counts.get(n, 0) + 1
            span_s[n] = span_s.get(n, 0.0) + (e - s) * 1e-9
    pieces = _innermost_pieces([x for x in spans if x[1] > w0 and x[0] < w1])
    devs = device_planes(pd)
    if not devs:
        raise ValueError("trace holds no TPU device plane")
    idle: dict = {}
    scope_s: dict = {}
    by_op: dict = {}
    copy_s: dict = {}
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
            if MOSAIC_OP in text and is_engine(kernels.get(name, set())):
                continue
            prog = modules[m][2] if modules and modules[m][0] <= s else None
            scope = scope_of(op_names.get(prog, {}).get(name, ""))
            scope_s[scope] = scope_s.get(scope, 0.0) + own * 1e-9
            ops = by_op.setdefault(scope, {})
            ops[name] = ops.get(name, 0.0) + own * 1e-9
            opcode, rest = _opcode(text)
            if opcode and opcode.group(1) == "copy" and scope != "kv_cache":
                shape = _SHAPE.search(text.split(" = ", 1)[-1])
                key = f"{shape.group(1)}[{shape.group(2)}]" if shape else "?"
                copy_s[key] = copy_s.get(key, 0.0) + own * 1e-9
        merged = _union([(s, e) for _, s, e in evs])
        edges = [w0] + [x for se in merged for x in se] + [w1]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        _name_gaps(gaps, pieces, idle)
    nd = len(devs)
    return {
        "program_span_counts": counts,
        "program_span_s": span_s,
        "program_idle_s": {k: v / nd for k, v in idle.items()},
        "scope_s": {k: v / nd for k, v in scope_s.items()},
        "scope_ops": {k: [[n, v / nd] for n, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:3]]
            for k, ops in by_op.items()},
        "copy_s": {k: v / nd for k, v in copy_s.items()},
    }


class ProgramTracer(Tracer):
    """The harness's tracer; its reduction adds ``reduce_program``'s keys
    to ``reduce_trace``'s, which it leaves as they are."""

    def reduce(self) -> dict:
        paths = glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError(f"no trace written under {self.out_dir}")
        with open(paths[0], "rb") as f:
            raw = f.read()
        kernels = mosaic_kernels(raw)
        pd = load(paths[0])
        red = reduce_trace(pd, kernels)
        red.update(reduce_program(pd, hlo_op_names(raw), kernels))
        shutil.rmtree(self.out_dir, ignore_errors=True)
        return red
