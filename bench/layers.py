"""Run one cell once with the profiler on, and read the program's own
spans, scopes and counters as well as the harness's.

    python bench/layers.py --workload <cell> --seed <n> --seconds <s>

It drives the cell as ``bench/run.py`` does, leaves out the comparison
with the reference, and prints one JSON line: every per-layer metric of
the cell in ``BENCHMARK.json``, the readers this script adds
(``LAYER_METRICS``, in ``bench/metrics``), the end-to-end numbers of the
traced run, and the reduced trace's program keys
(``benchlib.program_trace``). For training it also gives the untraced
window's step times beside the traced steps'; for serving, the host cost
of entering and leaving a span with the profiler off.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import numpy as np  # noqa: E402

import run  # noqa: E402
from benchlib import common  # noqa: E402

LAYER_METRICS = {
    "train": ("forward_ms.train", "backward_ms.train", "optimizer_ms.train"),
    "serve": ("cache_ms.serve", "readback_idle_ms.serve",
              "runtime_idle_ms.serve", "admit_wait_p83_ms.serve",
              "live_slot_share.serve"),
}
PROGRAM_KEYS = ("program_span_counts", "program_span_s", "program_idle_s",
                "scope_s", "scope_ops", "copy_s")


def train(cell, cfg, mix, seed, seconds, tracer) -> dict:
    from benchlib import train_cell
    from repro import reduce as R

    R.set_default_backend(cell["reduce_backend"])
    prog = train_cell.Program(cell, cfg, mix, seed)
    for _ in range(train_cell.CHECK_STEPS):
        prog.step()
    setup_s = time.perf_counter() - T_START

    def timed(n=None, until=None):
        ends = [time.perf_counter()]
        while (n is not None and len(ends) <= n) or (
                until is not None and ends[-1] - ends[0] < until):
            prog.step()
            ends.append(time.perf_counter())
        return np.diff(ends) * 1e3

    untraced = timed(until=seconds)
    with tracer:
        traced = timed(n=cell["trace_steps"])
    trace = tracer.reduce()
    prog.free()
    tokens = mix["batch"] * mix["seq"]
    return {"counters": {"setup_s": setup_s, "window_s": untraced.sum() / 1e3,
                         "tokens": len(untraced) * tokens, "seq": mix["seq"]},
            "trace": trace,
            "notes": {"step_ms_p50_untraced": float(np.median(untraced)),
                      "step_ms_p50_traced": float(np.median(traced)),
                      "steps_untraced": len(untraced)}}


def span_cost_us(n: int = 200_000) -> float:
    """Host time of entering and leaving one span, the profiler off."""
    from repro.runtime.metrics import span

    t = time.perf_counter()
    for _ in range(n):
        with span("serve.step"):
            pass
    return (time.perf_counter() - t) / n * 1e6


def serve(cell, cfg, mix, seed, seconds, tracer) -> dict:
    from benchlib import serve_cell, traffic
    from repro import reduce as R

    R.set_default_backend(cell["reduce_backend"])
    schedule = traffic.serve_schedule(mix, cfg["token_vocab"], seed, seconds)
    eng = serve_cell.build_engine(cell, cfg, mix, seed)
    setup_s = time.perf_counter() - T_START
    cost = span_cost_us()
    tracer.arm(cell["trace_seconds"], at=cell["trace_at"] * seconds)
    tracer.start()
    runtime, timed, due, t0, t_end = serve_cell.serve_window(
        eng, cell, schedule, tick=tracer.tick,
        canary_at=int(common.rng(seed, 4).integers(1, 9)))
    tracer.stop()
    trace = tracer.reduce()
    t = serve_cell.timings(runtime, timed, due, schedule)
    rec = runtime.requests()
    snap = runtime.metrics.snapshot()
    done = [x for x in t["tokens"] if x]
    return {"counters": {
        "setup_s": setup_s, "window_s": t_end - t0,
        "ttft_s": t["ttft"], "itl_s": t["itl"], "wait_s": t["wait"],
        "engine_steps": sum(len(w["ends"]) for w in timed.waves),
        "slots": cell["slots"], "prompt_len": mix["prompt_len"],
        "out_lens": [len(x) for x in done],
        "admit_wait_s": [r.launched - r.submitted for r in rec.values()
                         if r.launched is not None],
        "slot_steps": snap["slot_steps"],
        "live_slot_steps": snap["live_slot_steps"]},
        "trace": trace,
        "notes": {"span_enter_exit_us": cost, "failed": t["failed"],
                  "census_misses": timed.census_misses,
                  "requests": len(schedule), "waves": len(timed.waves),
                  "snapshot_ttft_p50_s": snap["ttft_p50_s"],
                  "snapshot_itl_p99_s": snap["itl_p99_s"]}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import jax

    from benchlib.program_trace import ProgramTracer
    from repro.launch.device import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = common.load("workloads", args.workload)
    devices, peaks = run.require_chips(cell["chips"])
    cfg = common.load("configs", cell["config"])
    mix = common.load("traffic", cell["traffic"])
    tracer = ProgramTracer(common.OUT / f"layers-{args.workload}")
    drive = {"train": train, "serve": serve}[cell["driver"]]
    res = drive(cell, cfg, mix, args.seed, args.seconds, tracer)
    r = {"counters": res["counters"], "trace": res["trace"],
         "model": cfg["model"], "peaks": peaks, "cell": cell, "mix": mix}
    manifest = common.manifest()
    names = [m["name"] for g in ("end_to_end", "per_layer")
             for m in run.cell_metrics(manifest, args.workload, g)]
    names += LAYER_METRICS[cell["driver"]]
    tr = res["trace"]
    out = {"metrics": {n: run.read_metric(n, r) for n in names},
           "device": {"kind": devices[0].device_kind,
                      "busy_s": tr["busy_s"], "window_s": tr["window_s"],
                      "engine_s": tr["engine_s"]},
           "span_counts": tr["span_counts"],
           "idle_by_span": tr["idle_by_span"],
           "top_ops": sorted(tr["ops"].items(), key=lambda kv: -kv[1])[:12],
           "notes": res["notes"]}
    out.update({k: tr[k] for k in PROGRAM_KEYS})
    out["copy_s"] = dict(sorted(tr["copy_s"].items(),
                                key=lambda kv: -kv[1])[:8])
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
