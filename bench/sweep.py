"""Find a serving cell's knee: serve its mix at several fixed rates, one
window each, in one process, and print for each rate what says whether
the system kept up.

    python bench/sweep.py --workload <cell> --rates 2,3,4 --seconds 40

A rate is sustained when the backlog does not grow through the window:
the last quarter of requests wait no longer than the first quarter by
more than a wave. The cell's rate is then set at about four fifths of the
highest rate sustained.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax
    import numpy as np

    import run
    from benchlib import common, serve_cell, traffic
    from benchlib.common import nearest_rank
    from repro import reduce as R
    from repro.launch.device import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = common.load("workloads", args.workload)
    run.require_chips(cell["chips"])
    cfg = common.load("configs", cell["config"])
    base = common.load("traffic", cell["traffic"])
    R.set_default_backend(cell["reduce_backend"])
    eng = serve_cell.build_engine(cell, cfg, base, args.seed)
    for rate in (float(x) for x in args.rates.split(",")):
        mix = dict(base, rate_per_s=rate)
        sched = traffic.serve_schedule(mix, cfg["token_vocab"], args.seed,
                                       args.seconds)
        t_start = time.perf_counter()
        runtime, timed, due, t0, t1 = serve_cell.serve_window(eng, cell,
                                                              sched)
        t = serve_cell.timings(runtime, timed, due, sched)
        q = max(1, len(sched) // 4)
        waves = [w["ends"][-1] - w["launch"] for w in timed.waves]
        print(json.dumps({
            "rate": rate, "requests": len(sched), "failed": t["failed"],
            "window_s": t1 - t0, "waves": len(waves),
            "wave_s_median": float(np.median(waves)),
            "prefill_s_median": float(np.median(
                [w["ends"][0] - w["launch"] for w in timed.waves])),
            "ttft_p50_ms": nearest_rank(t["ttft"], 50) * 1e3,
            "ttft_p83_ms": nearest_rank(t["ttft"], 83) * 1e3,
            "itl_p50_ms": nearest_rank(t["itl"], 50) * 1e3,
            "itl_p95_ms": nearest_rank(t["itl"], 95) * 1e3,
            "wait_first_quarter_s": float(np.mean(t["wait"][:q])),
            "wait_last_quarter_s": float(np.mean(t["wait"][-q:])),
            "served_per_s": (len(sched) - t["failed"]) / (t1 - t0),
            "seconds": time.perf_counter() - t_start,
        }), flush=True)


if __name__ == "__main__":
    main()
