"""``benchlib.program_trace``: the program's own spans and scopes in a
profiler trace -- the HLO op names read from the trace file, each
operation's scope, idle gaps cut by the innermost ``serve.*`` span, and
the readers of the metrics they feed -- on a hand-made trace whose
numbers are worked out by hand, and on a small trace recorded on a TPU
v5e (``data/tiny_program.xplane.pb``, made by
``record_program_trace.py``)."""

from __future__ import annotations

import pathlib
from types import SimpleNamespace as NS

import pytest

import run
from benchlib import program_trace as P
from benchlib import trace

DATA = pathlib.Path(__file__).parent / "data" / "tiny_program.xplane.pb"
MS = 1_000_000  # ns


def ev(name, start_ms, dur_ms, **stats):
    return NS(name=name, start_ns=int(start_ms * MS),
              duration_ns=int(dur_ms * MS), stats=stats)


CACHE_OP = "%fusion.1 = bf16[2,8]{1,0} fusion(bf16[2,8]{1,0} %p), kind=kLoop"
COPY_OP = "%copy.3 = bf16[4,8]{1,0} copy(bf16[4,8]{1,0} %c)"
KERNEL = ('%step.1 = f32[1,19]{1,0} custom-call(bf16[4,8]{1,0} %a), '
          'custom_call_target="tpu_custom_call"')
KERNELS = {"step.1": {"parts_accumulate_kernel"}}
# one instruction name in two programs, each with its own op name
OP_NAMES = {
    "jit_step(1)": {"fusion.1": "jit(step)/model/while/body/closed_call/"
                                "kv_cache/dynamic_slice"},
    "jit_step(2)": {"fusion.1": "jit(step)/census/reduce_sum;"
                                "jit(step)/census/add",
                    "step.1": "jit(step)/census/custom-call"},
}


def fake_trace():
    """Window 0-100 ms. Host: a wave 0-90 with two steps (dispatch, then
    read-back) 10-40 and 50-80, an admission 85-88, and the harness's own
    ``decode`` span. Device: program 1 runs 0-45 (the cache fusion
    12-30), program 2 45-100 (the census fusion 53-70, an unscoped copy
    70-75, an engine launch 75-78)."""
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev(trace.WINDOW, 0, 100), ev("decode", 10, 30),
        ev("serve.wave", 0, 90, wave=0, live=2),
        ev("serve.step", 10, 30), ev("serve.dispatch", 10, 5),
        ev("serve.readback", 15, 25),
        ev("serve.step", 50, 30), ev("serve.dispatch", 50, 2),
        ev("serve.readback", 52, 28), ev("serve.admit", 85, 3)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name=P.MODULE_LINE, events=[ev("jit_step(1)", 0, 45),
                                       ev("jit_step(2)", 45, 55)]),
        NS(name=trace.DEVICE_LINE, events=[
            ev(CACHE_OP, 12, 18), ev(CACHE_OP, 53, 17),
            ev(COPY_OP, 70, 5), ev(KERNEL, 75, 3)])])
    return NS(planes=[host, dev])


def test_reduce_program_hand_made_trace():
    red = P.reduce_program(fake_trace(), OP_NAMES, KERNELS)
    assert red["program_span_counts"] == {
        "serve.wave": 1, "serve.step": 2, "serve.dispatch": 2,
        "serve.readback": 2, "serve.admit": 1}
    assert red["program_span_s"]["serve.readback"] == pytest.approx(0.053)
    # idle 0-12, 30-53, 78-100, cut by the innermost span over each piece
    idle = red["program_idle_s"]
    assert idle == {
        "serve.wave": pytest.approx(0.027), "serve.dispatch":
        pytest.approx(0.004), "serve.readback": pytest.approx(0.013),
        "serve.admit": pytest.approx(0.003), P.OUTSIDE: pytest.approx(0.010)}
    # fusion.1 takes its scope from the program it ran in; the engine's
    # launch is left out
    assert red["scope_s"] == {"kv_cache": pytest.approx(0.018),
                              "census": pytest.approx(0.017),
                              "": pytest.approx(0.005)}
    assert red["scope_ops"]["census"] == [["fusion.1", pytest.approx(0.017)]]
    assert red["copy_s"] == {"bf16[4,8]": pytest.approx(0.005)}


def test_reduce_program_leaves_reduce_trace_as_it_is():
    """The harness's reduction of the same trace: its spans alone name the
    idle gaps, and the program's spans change no count."""
    red = trace.reduce_trace(fake_trace(), KERNELS)
    assert red["span_counts"] == {"decode": 1}
    assert red["engine_s"] == pytest.approx(0.003)
    assert sum(red["idle_by_span"].values()) == pytest.approx(0.057)


@pytest.mark.parametrize("name,scope", [
    ("jit(step)/jvp(forward)/dot_general", "jvp(forward)"),
    ("jit(step)/transpose(jvp(forward))/add_any", "transpose(jvp(forward))"),
    # remat's forward recomputed inside the backward stays backward
    ("jit(step)/transpose(jvp(forward))/jvp(forward)/checkpoint/"
     "rematted_computation/tanh", "transpose(jvp(forward))"),
    ("jit(step)/optimizer/mul", "optimizer"),
    ("jit(step)/model/while/body/closed_call/kv_cache/dynamic_slice",
     "kv_cache"),
    ("jit(step)/model/dot_general", "model"),
    ("jit(step)/census/reduce_sum;jit(step)/model/add", "census"),
    ("jit(step)/jvp()/while/body/dynamic_slice", ""),
    ("", ""),
])
def test_scope_of(name, scope):
    assert P.scope_of(name) == scope


# ---- a serialized trace file, made by hand ---------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """A message from (field, value): ints as varints, bytes and str as
    length-delimited."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _varint(f << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(f << 3 | 2) + _varint(len(v)) + v
    return out


def _instruction(name, op_name=None):
    fields = [(1, name), (2, "add")]
    if op_name is not None:
        fields.append((7, _msg((1, "add"), (2, op_name))))
    return _msg(*fields)


def test_hlo_op_names_from_a_trace_file():
    hlo = _msg((1, _msg((1, "jit_step"), (3, _msg(
        (1, "main"), (2, _instruction("add.1", "jit(step)/model/add")),
        (2, _instruction("add.2")))), (3, _msg(
            (1, "region"), (2, _instruction("add.3", "add")))))))
    stat = _msg((1, 7), (6, hlo))
    meta = _msg((1, 42), (2, "jit_step(42)"), (5, stat))
    plane = _msg((1, 1), (2, "/host:metadata"),
                 (4, _msg((1, 42), (2, meta))),
                 (5, _msg((1, 7), (2, _msg((1, 7), (2, "Hlo Proto"))))))
    other = _msg((1, 2), (2, "/device:TPU:0"))
    raw = _msg((1, other), (1, plane))
    assert P.hlo_op_names(raw) == {"jit_step(42)": {
        "add.1": "jit(step)/model/add", "add.3": "add"}}


# ---- the recorded chip trace ------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    raw = DATA.read_bytes()
    pd = trace.load(str(DATA))
    kernels = trace.mosaic_kernels(raw)
    names = P.hlo_op_names(raw)
    return (names, trace.reduce_trace(pd, kernels),
            P.reduce_program(pd, names, kernels))


def test_recorded_hlo_op_names(recorded):
    names, _, _ = recorded
    train = [m for p, m in names.items() if p.startswith("jit_guarded_step(")]
    serve = [m for p, m in names.items() if p.startswith("jit_step(")]
    assert len(train) == 1 and len(serve) == 2      # prefill and decode
    assert any("/transpose(jvp(forward))/" in n for n in train[0].values())


def test_recorded_scopes_cover_the_device_time(recorded):
    _, base, red = recorded
    s = red["scope_s"]
    for scope in ("jvp(forward)", "transpose(jvp(forward))", "optimizer",
                  "model", "kv_cache", "census"):
        assert s[scope] > 0, scope
    assert base["engine_s"] > 0
    # every operation's own time is in one scope or in the engine's launches
    assert sum(s.values()) + base["engine_s"] == pytest.approx(
        sum(base["ops"].values()))
    # unscoped work is a small rest: copies and transfers
    assert s[""] < 0.15 * sum(s.values())


def test_recorded_spans_nest_as_the_runtime_opens_them(recorded):
    _, base, red = recorded
    c = red["program_span_counts"]
    # a wave is one prefill; every engine call one dispatch and one
    # read-back; the harness's own spans wrap the same calls
    assert c["serve.wave"] == c["serve.prefill"] == base["span_counts"][
        "prefill"]
    assert c["serve.step"] == base["span_counts"]["decode"]
    assert c["serve.dispatch"] == c["serve.readback"] \
        == c["serve.prefill"] + c["serve.step"]
    assert c["serve.admit"] > 0
    assert not any(k.startswith(P.PREFIX) for k in base["span_counts"])


def test_recorded_idle_is_named_by_program_spans(recorded):
    _, base, red = recorded
    idle = red["program_idle_s"]
    assert sum(idle.values()) == pytest.approx(
        base["window_s"] - base["busy_s"], rel=1e-6)
    assert sum(idle.values()) == pytest.approx(
        sum(base["idle_by_span"].values()), rel=1e-6)
    named = sum(v for k, v in idle.items() if k.startswith(P.PREFIX))
    assert named >= 0.8 * base["idle_by_span"]["decode"]
    # waiting for arrivals is outside every serve.* span
    assert idle[P.OUTSIDE] >= base["idle_by_span"]["idle_wait"]


# ---- the readers -----------------------------------------------------------

def _r(trace_keys, counters=None):
    cell = {"slots": 2, "chips": 1}
    mix = {"prompt_len": 3, "output": {"max": 4}}
    model = {"n_layers": 4, "n_kv_heads": 2, "d_head": 8,
             "dtype": "bfloat16"}
    return {"trace": trace_keys, "counters": counters or {}, "cell": cell,
            "mix": mix, "model": model}


TRAIN = {"span_counts": {"step": 4}, "scope_s": {
    "jvp(forward)": 0.4, "transpose(jvp(forward))": 0.8, "optimizer": 0.2,
    "": 0.01}}
SERVE = {"program_span_counts": {"serve.prefill": 1, "serve.step": 9},
         "scope_s": {"kv_cache": 0.03, "model": 0.5},
         "copy_s": {"bf16[4,2,8,2,8]": 0.02, "bf16[4,8]": 0.5},
         "program_idle_s": {"serve.readback": 0.018, "serve.dispatch": 0.1,
                            "serve.wave": 0.006, "serve.step": 0.003,
                            P.OUTSIDE: 1.0}}


@pytest.mark.parametrize("name,keys,counters,value", [
    ("forward_ms.train", TRAIN, None, 100.0),
    ("backward_ms.train", TRAIN, None, 200.0),
    ("optimizer_ms.train", TRAIN, None, 50.0),
    # the kv_cache scope and the copies of a whole stacked cache
    # (4 layers, 2 slots, 3 + 4 + 1 positions, 2 heads of 8), per step
    ("cache_ms.serve", SERVE, None, 5.0),
    ("readback_idle_ms.serve", SERVE, None, 2.0),
    ("runtime_idle_ms.serve", SERVE, None, 1.0),
    ("admit_wait_p83_ms.serve", {}, {"admit_wait_s": [0.1 * i for i in
                                                      range(1, 7)]}, 500.0),
    ("live_slot_share.serve", {}, {"slot_steps": 40, "live_slot_steps": 10},
     25.0),
])
def test_readers(name, keys, counters, value):
    assert run.read_metric(name, _r(keys, counters)) == pytest.approx(value)


@pytest.mark.parametrize("name", [
    "forward_ms.train", "backward_ms.train", "optimizer_ms.train",
    "cache_ms.serve", "readback_idle_ms.serve", "runtime_idle_ms.serve",
    "admit_wait_p83_ms.serve", "live_slot_share.serve"])
def test_readers_find_nothing_in_a_trace_without_the_programs_marks(name):
    """The harness's reduction alone, as of a program that neither scopes
    its steps nor records its requests: every reader gives None."""
    red = trace.reduce_trace(fake_trace(), KERNELS)
    red["span_counts"]["step"] = 2
    assert run.read_metric(name, _r(red, {"wait_s": [1.0]})) is None
    assert run.read_metric(name, _r(None)) is None


# ---- the layer script's serving counters, on the tiny engine ---------------

class StubTracer:
    def arm(self, seconds, at):
        pass

    def start(self):
        pass

    def tick(self, elapsed):
        pass

    def stop(self):
        pass

    def reduce(self):
        return None


def test_layer_script_reads_the_runtimes_record_on_the_cpu():
    """The runtime's slot counters give the share the adapter's record
    gives, and a request waits no longer from its admission than from when
    it was due."""
    import _tiny
    import layers
    from repro import reduce as R

    cell, cfg, mix = _tiny.serve_cell()
    try:
        res = layers.serve(cell, cfg, mix, 7, 0.4, StubTracer())
    finally:
        R.set_default_backend(None)
    r = _r(None, res["counters"])
    r["cell"] = cell
    assert res["notes"]["failed"] == 0
    share = run.read_metric("live_slot_share.serve", r)
    assert share == pytest.approx(run.read_metric("slot_occupancy.serve", r))
    admit = run.read_metric("admit_wait_p83_ms.serve", r)
    wait = run.read_metric("queue_wait_p83_ms.serve", r)
    assert 0.0 <= admit <= wait
    assert len(res["counters"]["admit_wait_s"]) == res["notes"]["requests"]
