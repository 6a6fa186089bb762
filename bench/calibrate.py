"""Readings that set a cell's limits: the numbers ``correct`` compares,
from sound runs of the program on many seeds and from the control -- the
plain reference in float8 in the program's place -- on some of them, all
in one process at the cell's own size.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 1,2,3 [--seconds 15]

Prints one JSON line per seed: ``{"seed", "program": {...}, "control":
{...}}``. Training cells need no window; serving cells serve a short one
at the cell's own load, long enough to finish its longest requests.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def _half_batch(step):
    def faulty(p, o, g, batch):
        t = batch["tokens"]
        t = t[: t.shape[0] // 2] if t.shape[0] > 1 else t[:, : t.shape[1] // 2]
        return step(p, o, g, {"tokens": t})

    return faulty


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--fault", choices=("half_batch",), default=None,
                    help="plant a fault in the program's step: half of the "
                    "batch left out (half the rows, or of one row's tokens), "
                    "the mean taken over the rest")
    args = ap.parse_args(argv)
    import jax

    import run
    from benchlib import common, serve_cell, train_cell
    from repro import reduce as R
    from repro.launch.device import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = common.load("workloads", args.workload)
    devices, _ = run.require_chips(cell["chips"])
    cfg = common.load("configs", cell["config"])
    mix = common.load("traffic", cell["traffic"])
    control = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = {"seed": seed}
        if cell["driver"] == "train":
            R.set_default_backend(cell["reduce_backend"])
            prog = train_cell.Program(cell, cfg, mix, seed)
            if args.fault == "half_batch":
                prog.step_fn = _half_batch(prog.step_fn)
            readings = train_cell.program_readings(prog)
            prog.free()
            ref = train_cell.reference_readings(cfg, cell, mix, seed)
            out["program"] = train_cell.compare(readings, ref)
            if seed in control:
                low = train_cell.reference_readings(cfg, cell, mix, seed,
                                                    "fp8")
                out["control"] = train_cell.compare(low, ref)
        else:
            res = serve_cell.run(cell, cfg, mix, seed, args.seconds, None,
                                 time.perf_counter(), devices)
            out["program"] = res["numbers"]
            if seed in control:
                out["control"] = serve_cell.widest_gap(
                    cfg, seed, *res["check_batch"], prec="fp8")
            del res
        gc.collect()
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
