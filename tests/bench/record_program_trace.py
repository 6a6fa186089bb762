"""Record the small chip trace that ``test_bench_program_trace.py``
reduces.

    python tests/bench/record_program_trace.py \
        tests/bench/data/tiny_program.xplane.pb

On one TPU, inside the harness's ``window`` span: two guarded steps of a
tiny olmo configuration (the program's ``forward``, backward and
``optimizer`` scopes, the engine's kernels on, ``pallas_fused``), then a
tiny guarded serving window through ``ServingRuntime`` (the ``serve.*``
spans, the ``model``, ``kv_cache`` and ``census`` scopes). Copies the
trace file to the path given.
"""

from __future__ import annotations

import copy
import glob
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src"), str(ROOT / "tests"
                                                           / "bench")]


def main(dest: str) -> None:
    import jax

    import _tiny
    from benchlib import common, serve_cell, traffic, train_cell
    from benchlib.trace import Tracer
    from repro import reduce as R

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_program_trace: needs a TPU")
    cell = common.load("workloads", "olmo-1b.train.seq2048")
    cfg = copy.deepcopy(common.load("configs", "olmo-1b"))
    cfg["model"].update(n_layers=2, d_model=256, n_heads=2, n_kv_heads=2,
                        d_head=128, d_ff=512, vocab_size=512)
    cfg["token_vocab"] = 500
    mix = dict(common.load("traffic", "train-seq2048"), seq=128)
    R.set_default_backend("pallas_fused")
    prog = train_cell.Program(cell, cfg, mix, seed=1)
    prog.step()
    scell, scfg, smix = _tiny.serve_cell()
    schedule = traffic.serve_schedule(smix, scfg["token_vocab"], 1, 0.3)
    eng = serve_cell.build_engine(scell, scfg, smix, 1)
    out = tempfile.mkdtemp()
    tracer = Tracer(out)
    with tracer:
        prog.step()
        prog.step()
        serve_cell.serve_window(eng, scell, schedule)
    path = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(path, dest)
    print(dest, pathlib.Path(dest).stat().st_size)


if __name__ == "__main__":
    main(sys.argv[1])
