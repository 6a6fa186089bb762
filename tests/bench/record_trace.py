"""Record the small chip trace that ``test_bench_trace.py`` reduces.

    python tests/bench/record_trace.py tests/bench/data/tiny_train.xplane.pb

Runs three guarded steps of the tiny olmo configuration on one TPU, the
last two inside the harness's ``window`` span and its ``data``/``step``/
``sync`` spans, with the engine's kernels on (``pallas_fused``), and
copies the trace file to the path given.
"""

from __future__ import annotations

import glob
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]


def main(dest: str) -> None:
    import copy

    import jax

    from benchlib import common, train_cell
    from benchlib.trace import Tracer
    from repro import reduce as R

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: needs a TPU")
    cell = common.load("workloads", "olmo-1b.train.seq2048")
    cfg = copy.deepcopy(common.load("configs", "olmo-1b"))
    cfg["model"].update(n_layers=2, d_model=256, n_heads=2, n_kv_heads=2,
                        d_head=128, d_ff=512, vocab_size=512)
    cfg["token_vocab"] = 500
    mix = dict(common.load("traffic", "train-seq2048"), seq=128)
    R.set_default_backend("pallas_fused")
    prog = train_cell.Program(cell, cfg, mix, seed=1)
    prog.step()
    out = tempfile.mkdtemp()
    tracer = Tracer(out)
    with tracer:
        prog.step()
        prog.step()
    path = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(path, dest)
    print(dest)


if __name__ == "__main__":
    main(sys.argv[1])
