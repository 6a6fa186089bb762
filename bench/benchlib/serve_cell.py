"""Serving cells: ``ServingRuntime`` over the program's ``GuardedEngine``,
offered open-loop traffic at the mix's fixed rate.

Requests are submitted as they fall due -- also while a wave runs, at each
engine call -- and waves run through the runtime's own loop (``drain``). A
thin adapter over the engine protocol records, per wave, when its prefill
was launched and when each step's tokens came back, so every request's
first token, later tokens and queue wait are timed from when it was due.
It also tests the engine's census of non-finite logits in the window:
once, it poisons one slot through the runtime's own chaos hook (a logit
scale of NaN), and the census has to name that slot and no other.
"""

from __future__ import annotations

import gc
import time

import numpy as np
from jax.profiler import TraceAnnotation

from benchlib import common, reference, traffic, weights


class TimedEngine:
    """The engine protocol, passed through, with the time of every call.

    At the first decode call from the ``canary_at``-th on that has a slot
    no request is using -- never filled, or its request done -- the last
    such slot gets the logit scale NaN. The census must flag that slot
    then, and no slot on any call; ``census_misses`` counts each slot it
    got wrong. The runtime acts only on the census of live slots, so the
    canary changes no token and no step: a live slot's poisoned step is
    retried while the poisoned attempt's caches are still held, which at
    the chat cell's size does not fit in the chip's memory."""

    def __init__(self, engine, clock, before_call=None, canary_at=None):
        self.engine = engine
        self.slots = engine.slots
        self.clock = clock
        self.before_call = before_call
        self.canary_at = canary_at
        self.rid_of: dict = {}     # id(prompt) -> rid
        self.max_new: dict = {}    # rid -> max_new
        self.waves: list = []      # {"rids", "launch", "ends"}
        self.decodes = 0
        self.wave_t = 0            # decode calls into the current wave
        self.planted = 0
        self.census_misses = 0

    def validate(self, prompt, max_new):
        return self.engine.validate(prompt, max_new)

    def _before(self):
        if self.before_call is not None:
            self.before_call()

    def _census(self, census, expect: set):
        flagged = {i for i in range(self.slots) if float(census[i]) > 0.0}
        self.census_misses += len(flagged ^ expect)

    def start_wave(self, prompts, scales, backend):
        self._before()
        launch = self.clock()
        with TraceAnnotation("prefill"):
            out = self.engine.start_wave(prompts, scales, backend)
        rids = [None if p is None else self.rid_of[id(p)] for p in prompts]
        self.waves.append({"rids": rids, "launch": launch,
                           "ends": [self.clock()]})
        self.wave_t = 0
        self._census(out[2], set())
        return out

    def decode(self, state, scales, backend):
        self._before()
        self.decodes += 1
        self.wave_t += 1
        expect = set()
        if self.canary_at is not None and not self.planted \
                and self.decodes >= self.canary_at:
            rids = self.waves[-1]["rids"] + [None] * self.slots
            idle = [s for s in range(self.slots) if rids[s] is None
                    or self.wave_t >= self.max_new[rids[s]]]
            if idle:
                scales = list(scales)
                scales[idle[-1]] = float("nan")
                expect = {idle[-1]}
                self.planted += 1
        with TraceAnnotation("decode"):
            out = self.engine.decode(state, scales, backend)
        self._census(out[2], expect)
        self.waves[-1]["ends"].append(self.clock())
        return out


def model_config(cfg: dict):
    from repro.configs import ModelConfig

    return ModelConfig(**cfg["model"])


def build_engine(cell: dict, cfg: dict, mix: dict, seed: int):
    """The program's engine with the benchmark's weights, warmed on the
    cell's own shapes (one prefill and one decode on the first backend of
    the runtime's chain)."""
    from repro.launch.serve import GuardedEngine
    from repro.runtime.serving import DEFAULT_BACKEND_CHAIN

    model = model_config(cfg)
    s_max = mix["prompt_len"] + traffic.max_new_tokens(mix) + 1
    eng = GuardedEngine(model, s_max, cell["slots"])
    eng.params = None
    gc.collect()
    eng.params = weights.make_params(model, common.jax_key(seed))
    warm = [np.zeros(mix["prompt_len"], np.int32)] * cell["slots"]
    ones = [1.0] * cell["slots"]
    backend = DEFAULT_BACKEND_CHAIN[0]
    state, _, _ = eng.start_wave(warm, ones, backend)
    _, tok, _ = eng.decode(state, ones, backend)
    del state
    return eng


def serve_window(eng, cell: dict, schedule: list, clock=time.perf_counter,
                 tick=None, sleep=time.sleep, canary_at=None):
    """Offer the schedule open loop; returns (runtime, adapter, due times,
    t0, t_end). ``tick`` is told the time since the window began at every
    engine call and every arrival; ``canary_at`` is the adapter's."""
    from repro.runtime.serving import Request, ServingRuntime

    due = [None] * len(schedule)
    state = {"i": 0}
    timed = TimedEngine(eng, clock, canary_at=canary_at)
    runtime = ServingRuntime(timed, queue_capacity=cell["queue_capacity"],
                             clock=clock)
    t0 = clock()

    def feed():
        now = clock()
        if tick is not None:
            tick(now - t0)
        with TraceAnnotation("admit"):
            while state["i"] < len(schedule) and (
                    t0 + schedule[state["i"]][0] <= now):
                i = state["i"]
                _, prompt, max_new = schedule[i]
                timed.rid_of[id(prompt)] = i
                timed.max_new[i] = max_new
                due[i] = t0 + schedule[i][0]
                runtime.submit(Request(rid=i, prompt=prompt,
                                       max_new=max_new))
                state["i"] += 1

    timed.before_call = feed
    while state["i"] < len(schedule) or len(runtime.queue):
        feed()
        if len(runtime.queue):
            runtime.drain()
        elif state["i"] < len(schedule):
            with TraceAnnotation("idle_wait"):
                sleep(max(0.0, t0 + schedule[state["i"]][0] - clock()))
    return runtime, timed, due, t0, clock()


def timings(runtime, timed: TimedEngine, due: list, schedule: list) -> dict:
    """Per-request times from the adapter's record. A request that did not
    complete counts as missing (``inf``) in every latency."""
    results = runtime._results
    slot_of = {}
    for w, wave in enumerate(timed.waves):
        for s, rid in enumerate(wave["rids"]):
            if rid is not None:
                slot_of[rid] = w
    ttft, wait, itl, tokens = [], [], [], []
    failed = 0
    for rid in range(len(schedule)):
        res = results.get(rid)
        ok = res is not None and res.ok and rid in slot_of
        if not ok:
            failed += 1
            ttft.append(float("inf"))
            wait.append(float("inf"))
            tokens.append(None)
            continue
        wave = timed.waves[slot_of[rid]]
        n = len(res.tokens)
        ends = wave["ends"][:n]
        ttft.append(ends[0] - due[rid])
        wait.append(wave["launch"] - due[rid])
        itl.extend(np.diff(ends).tolist())
        tokens.append(list(res.tokens))
    return {"ttft": ttft, "wait": wait, "itl": itl, "tokens": tokens,
            "failed": failed}


def sample_requests(tokens: list, seed: int, n: int) -> list:
    """The longest completed request and others drawn from the seed."""
    done = [i for i, t in enumerate(tokens) if t]
    if not done:
        return []
    longest = max(done, key=lambda i: len(tokens[i]))
    rest = [i for i in done if i != longest]
    g = common.rng(seed, 3)
    pick = g.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[j] for j in sorted(pick)]


def check_batch(schedule: list, tokens: list, picks: list, total: int):
    """The sampled requests as one (n, total) block of prompt + served
    tokens, padded at the end, with the positions that predicted a served
    token and the token served there."""
    n = len(picks)
    seqs = np.zeros((n, total), np.int32)
    mask = np.zeros((n, total), bool)
    served = np.zeros((n, total), np.int32)
    for r, rid in enumerate(picks):
        prompt = schedule[rid][1]
        toks = np.asarray(tokens[rid], np.int32)
        plen, k = len(prompt), len(toks)
        seqs[r, :plen] = prompt
        seqs[r, plen:plen + k] = toks
        mask[r, plen - 1:plen - 1 + k] = True
        served[r, plen - 1:plen - 1 + k] = toks
    return seqs, mask, served


def widest_gap(cfg: dict, seed: int, seqs, mask, served,
               prec: str = "f32") -> dict:
    """Run the plain reference once over the sampled sequences. The gap of
    a served token is how far its reference logit lies below the
    reference's best at that position; the number is the widest gap.
    With ``prec="fp8"`` the control puts its own arg-max in the served
    tokens' place, and its gap is read from the float32 reference."""
    key = common.jax_key(seed)
    picks = served
    if prec != "f32":
        low = reference.ServeReference(cfg["model"], key, prec)
        _, _, picks = low.read(seqs, served)
        del low
        gc.collect()
    ref = reference.ServeReference(cfg["model"], key)
    best, picked, _ = ref.read(seqs, picks)
    del ref
    gc.collect()
    g = (best - picked)[mask]
    return {"token_gap": float(g.max()), "tokens_checked": int(mask.sum())}


def run(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float,
        tracer, t_start: float, devices) -> dict:
    from repro import reduce as R

    R.set_default_backend(cell["reduce_backend"])
    schedule = traffic.serve_schedule(mix, cfg["token_vocab"], seed, seconds)
    eng = build_engine(cell, cfg, mix, seed)
    setup_s = time.perf_counter() - t_start
    trace = None
    if tracer is not None:
        tracer.arm(cell["trace_seconds"], at=cell["trace_at"] * seconds)
        tracer.start()
    canary_at = int(common.rng(seed, 4).integers(1, 9))
    runtime, timed, due, t0, t_end = serve_window(
        eng, cell, schedule, tick=(tracer.tick if tracer else None),
        canary_at=canary_at)
    if tracer is not None:
        tracer.stop()
        trace = tracer.reduce()
    t = timings(runtime, timed, due, schedule)
    peak = common.memory_peak(devices)
    retries = runtime.metrics.snapshot()["retries"]
    eng.params = None
    del eng, runtime
    gc.collect()
    picks = sample_requests(t["tokens"], seed, cell["check_requests"])
    total = mix["prompt_len"] + traffic.max_new_tokens(mix)
    seqs, mask, served = check_batch(schedule, t["tokens"], picks, total)
    # a window in which no canary was planted has not tested the census
    numbers = dict(widest_gap(cfg, seed, seqs, mask, served),
                   failed_requests=t["failed"],
                   census_misses=timed.census_misses + (timed.planted == 0))
    done = [x for x in t["tokens"] if x]
    return {
        "attempted": len(schedule),
        "failed": t["failed"],
        "counters": {
            "setup_s": setup_s, "window_s": t_end - t0,
            "ttft_s": t["ttft"], "itl_s": t["itl"], "wait_s": t["wait"],
            "engine_steps": sum(len(w["ends"]) for w in timed.waves),
            "slots": cell["slots"], "prompt_len": mix["prompt_len"],
            "out_lens": [len(x) for x in done],
        },
        "trace": trace,
        "memory_peak_bytes": peak,
        "numbers": numbers,
        "check_batch": (seqs, mask, served),
        "notes": {
            "requests": len(schedule), "waves": len(timed.waves),
            "canary_at": canary_at, "retries": retries,
            "wave_s_p50": float(np.median(
                [w["ends"][-1] - w["launch"] for w in timed.waves])),
            "prefill_s_p50": float(np.median(
                [w["ends"][0] - w["launch"] for w in timed.waves])),
            "itl_ms_p50": float(np.median(t["itl"])) * 1e3,
            "ttft_ms_p50": float(np.median(t["ttft"])) * 1e3,
            "tail_s": t_end - t0 - seconds},
    }
