"""Serving cells of MLA + MoE configurations (DeepSeek-V2): ``serve_cell``'s
engine, window, adapter, timings and sampling over the program's
``GuardedEngine``, with the plain reference of
``benchlib.reference_mla_moe`` for the comparison, the engine's
routed-pair counters, and a tracer that adds the MLA and MoE scopes'
device time (``benchlib.moe_trace``).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchlib import common, reference_mla_moe, serve_cell, traffic


def build_engine(cell: dict, cfg: dict, mix: dict, seed: int):
    """``serve_cell.build_engine`` (the configuration's nested MLA, MoE and
    YaRN settings build in ``ModelConfig``), its counters zeroed after the
    warm-up."""
    eng = serve_cell.build_engine(cell, cfg, mix, seed)
    eng.moe_held_pairs = eng.moe_decode_calls = 0
    return eng


def widest_gap(cfg: dict, seed: int, seqs, mask, served,
               prec: str = "f32") -> dict:
    """``serve_cell.widest_gap`` against ``reference_mla_moe``."""
    key = common.jax_key(seed)
    picks = served
    if prec != "f32":
        low = reference_mla_moe.ServeReference(cfg["model"], key, prec)
        _, _, picks = low.read(seqs, served)
        del low
        gc.collect()
    ref = reference_mla_moe.ServeReference(cfg["model"], key)
    best, picked, _ = ref.read(seqs, picks)
    del ref
    gc.collect()
    g = (best - picked)[mask]
    return {"token_gap": float(g.max()), "tokens_checked": int(mask.sum())}


def run(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float,
        tracer, t_start: float, devices) -> dict:
    from repro import reduce as R

    from benchlib.moe_trace import MoETracer

    R.set_default_backend(cell["reduce_backend"])
    schedule = traffic.serve_schedule(mix, cfg["token_vocab"], seed, seconds)
    eng = build_engine(cell, cfg, mix, seed)
    setup_s = time.perf_counter() - t_start
    trace = None
    if tracer is not None:
        tracer = MoETracer(tracer.out_dir, cfg["model"])
        tracer.arm(cell["trace_seconds"], at=cell["trace_at"] * seconds)
        tracer.start()
    canary_at = int(common.rng(seed, 4).integers(1, 9))
    runtime, timed, due, t0, t_end = serve_cell.serve_window(
        eng, cell, schedule, tick=(tracer.tick if tracer else None),
        canary_at=canary_at)
    if tracer is not None:
        tracer.stop()
        trace = tracer.reduce()
    t = serve_cell.timings(runtime, timed, due, schedule)
    peak = common.memory_peak(devices)
    retries = runtime.metrics.snapshot()["retries"]
    pairs, calls = eng.moe_held_pairs, eng.moe_decode_calls
    eng.params = None
    del eng, runtime
    gc.collect()
    picks = serve_cell.sample_requests(t["tokens"], seed,
                                       cell["check_requests"])
    total = mix["prompt_len"] + traffic.max_new_tokens(mix)
    seqs, mask, served = serve_cell.check_batch(schedule, t["tokens"], picks,
                                                total)
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
            "moe_held_pairs": pairs, "moe_decode_calls": calls,
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
            "tail_s": t_end - t0 - seconds,
            "held_pairs_per_decode": pairs / max(calls, 1)},
    }
