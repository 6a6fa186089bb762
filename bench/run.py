"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

The cell (``bench/workloads/<cell>.json``) names its configuration
(``bench/configs``), its traffic mix (``bench/traffic``) and its driver.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, each computed by its reader in ``bench/metrics``. A run
needs TPUs: with no TPU, an unknown chip or fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from benchlib import common  # noqa: E402
from benchlib.peaks import peaks_for  # noqa: E402

def cell_metrics(manifest: dict, cell: str, group: str) -> list:
    """The manifest's metrics of ``group`` that this cell reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


def read_metric(name: str, r: dict):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(r)


def require_chips(chips: int):
    """The devices of a run, or exit: TPUs only, of a known kind, enough of
    them. Never a fallback."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU; JAX reports {devs[0].platform}")
    try:
        peaks = peaks_for(devs[0].device_kind)
    except ValueError as e:
        sys.exit(f"bench: {e}")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips; {len(devs)} found")
    return devs, peaks


def verdict(cell: dict, res: dict):
    """``correct`` and the numbers compared beside their limits: correct
    when every number is within its limit."""
    check = {k: {"value": res["numbers"][k], "limit": v}
             for k, v in cell["limits"].items()}
    return all(c["value"] <= c["limit"] for c in check.values()), check


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             devices, peaks, t_start: float) -> dict:
    """Drive the cell and compute its metrics and verdict."""
    import importlib

    from benchlib.trace import Tracer, breakdown

    manifest = common.manifest()
    cell = common.load("workloads", name)
    cfg = common.load("configs", cell["config"])
    mix = common.load("traffic", cell["traffic"])
    # a cell's driver is found by name: benchlib/<driver>_cell.py
    driver = importlib.import_module(f"benchlib.{cell['driver']}_cell")
    tracer = Tracer(common.OUT / f"trace-{name}") if trace else None
    res = driver.run(cell, cfg, mix, seed, seconds, tracer, t_start,
                     devices[: cell["chips"]])
    r = {"counters": res["counters"], "trace": res["trace"],
         "model": cfg["model"], "peaks": peaks, "cell": cell, "mix": mix}
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(manifest, name, group):
        v = read_metric(m["name"], r)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    correct, check = verdict(cell, res)
    out = {"correct": bool(correct), "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": {
               "platform": devices[0].platform,
               "kind": devices[0].device_kind, "count": len(devices),
               "memory_peak_bytes": res["memory_peak_bytes"]}}
    if res["trace"] is not None:
        out["device"]["busy_s"] = res["trace"]["busy_s"]
        out["device"]["window_s"] = res["trace"]["window_s"]
        out["breakdown"] = breakdown(res["trace"])
    out["check"] = check
    out["notes"] = res.get("notes", {})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = common.load("workloads", args.workload)
    from repro.launch.device import use_compile_cache

    import jax

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices, peaks = require_chips(cell["chips"])
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   devices, peaks, T_START)
    for k, v in out.pop("notes").items():
        print(f"bench: {k} {v!r}", file=sys.stderr)
    for k, c in out["check"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
