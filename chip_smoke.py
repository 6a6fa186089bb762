"""Chip smoke: the main path once, on one TPU, at full olmo-1b width.

    python chip_smoke.py                # device, engine, train, serve
    python chip_smoke.py --four-chips   # the data-parallel guarded train
                                        # step on four chips vs one device

Phases, each printing one line of its own:

  device  -- device 0 must be a TPU whose ``device_kind`` is in the peaks
             table (``repro.core.cost_model.PEAKS``); anything else exits
             non-zero here, before any result is printed.
  engine  -- ``repro.reduce`` on ``backend="pallas_fused"`` at olmo-1b leaf
             sizes: reduce (sum, norm2, Kahan; bf16 and f32), reduce_tree
             over the full parameter tree (census, and the [(), clip]
             epilogue fork), reduce_many and scan. Each result is compared
             with a float64 numpy reference under the budgets of
             ``tests/harness.py``, and each compiled program must hold a
             ``tpu_custom_call`` (the kernel ran compiled, not interpreted).
  train   -- ``repro.launch.train.main``: 4 guarded steps at batch 1 x seq
             2048 on pallas_fused, then the same run on xla; every loss
             finite, nothing skipped, step-1 losses within 1e-2 relative.
  serve   -- ``repro.launch.serve.main --guard``: 8 requests, no breaker
             trip, no quarantine, tokens identical to the plain loop.

``--four-chips`` runs only ``train.main --mesh --guard`` (global batch 4 x
512 on pallas_fused) over every device, then the same global batch on a
one-device mesh, and compares loss, grad norm and skip decisions per step.

The last line of standard output is one JSON object naming the device.
Any failed check raises, so the script exits non-zero. ``--tiny`` swaps in
the tiny configuration and small shapes (it still needs a TPU).
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "tests")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import harness  # noqa: E402  (tests/harness.py: the engine's error budgets)
from repro import reduce as R  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.core import cost_model  # noqa: E402
from repro.launch import serve, train  # noqa: E402
from repro.launch.device import device_report, use_compile_cache  # noqa: E402
from repro.models import init_params  # noqa: E402

BACKEND = "pallas_fused"
CLIP = 1.0
SIZES = {
    False: {"seq": 2048, "prompt_len": 128, "max_new": 32, "mesh_seq": 512},
    True: {"seq": 64, "prompt_len": 16, "max_new": 8, "mesh_seq": 32},
}


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def require_compiled(fn, *args) -> None:
    """The kernel in ``fn`` was compiled for the chip, not interpreted."""
    text = fn.lower(*args).compile().as_text()
    check("tpu_custom_call" in text, "no tpu_custom_call in the program")


def phase_device() -> dict:
    rep = device_report()
    if rep["platform"] != "tpu":
        raise SystemExit(f"device: no TPU; JAX reports {rep}")
    cost_model.peaks_for(rep["kind"])  # a kind without peaks is an error
    cores = getattr(jax.devices()[0], "num_cores", None)
    print(f"device: ok platform={rep['platform']} kind={rep['kind']} "
          f"count={rep['count']} num_cores={cores}")
    return rep


def _close(got, want, tol, what) -> float:
    err = abs(float(got) - float(want))
    check(err <= tol, f"{what}: |{float(got)} - {float(want)}| > {tol}")
    return err


def _tree_norm_budget(sumsq: float, compute_dtype: str) -> float:
    """``harness.budget_for``'s norm2 budget for a whole tree's mass."""
    rel = harness.COMPUTE_REL[compute_dtype]
    return rel * sumsq / (2.0 * np.sqrt(sumsq)) + 1e-6


def phase_engine(tiny: bool, seed: int) -> None:
    cfg = get_arch("olmo-1b", tiny=tiny)
    key = jax.random.PRNGKey(seed)
    x32 = jax.random.normal(key, (cfg.d_model, cfg.d_ff), jnp.float32)
    x16 = x32.astype(jnp.bfloat16)
    n_checks = 0
    for x in (x16, x32):
        for kind in ("sum", "norm2"):
            fn = jax.jit(lambda v, k=kind: R.reduce(v, kind=k, backend=BACKEND))
            require_compiled(fn, x)
            plan = R.plan_for(x.shape, x.dtype, kind=kind, backend=BACKEND)
            _close(fn(x), harness.oracle(x, kind),
                   harness.budget_for(x, kind, plan), f"reduce {kind} {x.dtype}")
            n_checks += 1
    kahan = jax.jit(lambda v: R.reduce(v, backend=BACKEND, precision="kahan",
                                       compute_dtype="float32"))
    require_compiled(kahan, x32)
    _close(kahan(x32), harness.oracle(x32, "sum"),
           harness.budget_for(x32, "sum", compute_dtype="float32"), "kahan")

    params, _ = init_params(key, cfg)
    leaves = jax.tree.leaves(params)
    sumsq = sum(float(np.square(np.asarray(v, np.float64)).sum())
                for v in leaves)
    want_norm = np.sqrt(sumsq)
    norm_tol = _tree_norm_budget(sumsq, "bfloat16")
    census = jax.jit(lambda t: R.reduce_tree(t, "norm2", backend=BACKEND,
                                             census=True))
    require_compiled(census, params)
    norm, counts = census(params)
    _close(norm, want_norm, norm_tol, "reduce_tree norm2 census")
    check(float(np.asarray(counts).sum()) == 0.0, "census counted non-finite")
    fork = jax.jit(lambda t: R.reduce_tree(
        t, "norm2", backend=BACKEND, epilogue=[(), ("clip_coeff", CLIP)]))
    require_compiled(fork, params)
    norm_f, clip = np.asarray(fork(params), np.float64)
    _close(norm_f, want_norm, norm_tol, "reduce_tree fork norm")
    _close(clip, min(1.0, CLIP / want_norm), 1e-2 * min(1.0, CLIP / want_norm),
           "reduce_tree fork clip")

    many_in = [params["embed"]["table"], x16, x32[0]]
    many = jax.jit(lambda a: R.reduce_many(a, backend=BACKEND))
    require_compiled(many, many_in)
    for got, v in zip(np.asarray(many(many_in)), many_in):
        _close(got, harness.oracle(v, "sum"), harness.budget_for(v, "sum"),
               f"reduce_many {v.shape}")
    del params, leaves, many_in

    flat = x32.reshape(-1)
    scan = jax.jit(lambda v: R.scan(v, backend=BACKEND))
    require_compiled(scan, flat)
    plan = R.scan_plan_for(flat.shape, flat.dtype, backend=BACKEND)
    err = np.abs(np.asarray(scan(flat), np.float64) - harness.scan_oracle(flat))
    check(bool((err <= harness.scan_budget(flat, plan.compute_dtype)).all()),
          f"scan: max error {float(err.max())}")
    print(f"engine: ok reduce x{n_checks} kahan reduce_tree(census, fork) "
          f"reduce_many scan, all compiled, leaf {x32.shape}")


def _train_argv(tiny: bool, *extra: str) -> list:
    return ["--arch", "olmo-1b", "--guard", "--log-every", "1",
            *(("--tiny",) if tiny else ()), *extra]


def phase_train(tiny: bool) -> None:
    argv = _train_argv(tiny, "--batch", "1", "--seq", str(SIZES[tiny]["seq"]),
                       "--steps", "4")
    kernel = train.main(argv + ["--reduce-backend", BACKEND])
    gc.collect()  # the first run's state is gone before the second starts
    plain = train.main(argv + ["--reduce-backend", "xla"])
    R.set_default_backend(None)
    gc.collect()
    for rec in kernel:
        check(bool(np.isfinite(rec["loss"])), f"loss finite: {rec}")
        check(not rec["skipped"] and rec["nonfinite"] == 0.0,
              f"step not skipped, census clean: {rec}")
    l1, l1_xla = kernel[0]["loss"], plain[0]["loss"]
    check(abs(l1 - l1_xla) <= 1e-2 * abs(l1_xla),
          f"step-1 loss {l1} vs xla {l1_xla}")
    print(f"train: ok 4 guarded steps on {BACKEND}, losses "
          f"{[r['loss'] for r in kernel]}, step-1 xla {l1_xla}")


def phase_serve(tiny: bool) -> None:
    s = SIZES[tiny]
    argv = ["--arch", "olmo-1b", "--requests", "8", "--batch-slots", "4",
            "--prompt-len", str(s["prompt_len"]),
            "--max-new", str(s["max_new"]), *(("--tiny",) if tiny else ())]
    results, snap = serve.main(argv + ["--guard"])
    gc.collect()
    check(len(results) == 8 and all(r.ok for r in results),
          f"all 8 requests complete: {results}")
    check(snap["breaker_trips"] == 0 and snap["quarantined"] == 0,
          f"no breaker trip or quarantine: {snap}")
    plain = serve.main(argv)
    gc.collect()
    check([list(r.tokens) for r in results] == plain,
          "guarded tokens identical to the plain loop")
    print(f"serve: ok 8 guarded requests x {s['max_new']} tokens, 0 breaker "
          f"trips, tokens identical to the plain loop")


def phase_four_chips(tiny: bool) -> None:
    world = len(jax.devices())
    check(world == 4, f"four devices, found {world}")
    argv = _train_argv(tiny, "--batch", "4", "--seq",
                       str(SIZES[tiny]["mesh_seq"]), "--steps", "3",
                       "--reduce-backend", BACKEND)
    mesh4 = train.main(argv + ["--mesh"])
    gc.collect()
    mesh1 = train.main(argv + ["--mesh", "1"])
    R.set_default_backend(None)
    gc.collect()
    for a, b in zip(mesh4, mesh1):
        for key in ("loss", "grad_norm"):
            check(abs(a[key] - b[key]) <= 1e-2 * abs(b[key]),
                  f"step {a['step']} {key}: 4 devices {a[key]} vs 1 {b[key]}")
        check(a["skipped"] == b["skipped"], f"step {a['step']} skip decision")
    check(all(r["param_devices"] == world for r in mesh4),
          "params replicated on every mesh device")
    check(len(mesh4) == len(mesh1) == 3, "3 steps each")
    print(f"four_chips: ok {world}-device guarded mesh vs 1 device, losses "
          f"{[r['loss'] for r in mesh4]} vs {[r['loss'] for r in mesh1]}, "
          f"grad norms {[r['grad_norm'] for r in mesh4]} vs "
          f"{[r['grad_norm'] for r in mesh1]}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data-parallel guarded train path "
                    "and its one-device comparison")
    ap.add_argument("--tiny", action="store_true",
                    help="tiny configuration and small shapes")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()
    rep = phase_device()
    if args.four_chips:
        phase_four_chips(args.tiny)
    else:
        phase_engine(args.tiny, args.seed)
        phase_train(args.tiny)
        phase_serve(args.tiny)
    print(json.dumps({"ok": True, "device": rep}))


if __name__ == "__main__":
    main()
