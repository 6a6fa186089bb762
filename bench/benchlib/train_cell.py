"""Training cells: the program's guarded train step, driven from the seed.

Set-up builds one object -- the compiled step with its state -- and drives
it through its first three steps with the window's own call and feed, on
rows that all differ. Those steps are read for the comparison with the
plain reference; then the same object runs the measured window. Each step
repeats the body of ``repro.launch.train.main``: next batch, step, read the
loss and the skip flag.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from benchlib import common, reference, traffic, weights

CHECK_STEPS = 3


def _stacked(path: str) -> bool:
    return path.startswith("['units']")


def _flat(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(p), x) for p, x in flat]


_norms = jax.jit(lambda x: jnp.sqrt(jnp.sum(
    jnp.square(x.astype(jnp.float32)), axis=tuple(range(1, x.ndim)))))
_norm = jax.jit(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))


def leaf_norms(tree, scale: float = 1.0) -> dict:
    """Norm of every leaf, and of every layer of a stacked leaf."""
    out = {}
    for path, x in _flat(tree):
        if _stacked(path):
            for i, v in enumerate(np.asarray(_norms(x))):
                out[f"{path}[{i}]"] = float(v) * scale
        else:
            out[path] = float(_norm(x)) * scale
    return out


_CHANGE: dict = {}


def change_norms(params, key) -> dict:
    """Norm of every leaf's (and layer's) change from the weights the
    benchmark made, regenerated one layer at a time."""
    out = {}
    for path, x in _flat(params):
        sig = (path, x.shape, str(x.dtype))
        if sig not in _CHANGE:
            if _stacked(path):
                def fn(p, k, path=path):
                    def one(i):
                        p0 = weights.layer_of(k, path, p.shape, p.dtype, i)
                        d = p[i].astype(jnp.float32) - p0.astype(jnp.float32)
                        return jnp.sqrt(jnp.sum(d * d))
                    return jax.lax.map(one, jnp.arange(p.shape[0]))
            else:
                def fn(p, k, path=path):
                    p0 = weights.layer_of(k, path, p.shape, p.dtype, None)
                    d = p.astype(jnp.float32) - p0.astype(jnp.float32)
                    return jnp.sqrt(jnp.sum(d * d))
            _CHANGE[sig] = jax.jit(fn)
        v = np.asarray(_CHANGE[sig](x, key))
        if _stacked(path):
            for i, n in enumerate(v):
                out[f"{path}[{i}]"] = float(n)
        else:
            out[path] = float(v)
    return out


def model_config(cfg: dict):
    from repro.configs import ModelConfig

    return ModelConfig(**cfg["model"])


class Program:
    """The system under test: the guarded step, its state and its feed."""

    def __init__(self, cell: dict, cfg: dict, mix: dict, seed: int):
        from repro import optim
        from repro.configs import TrainConfig
        from repro.launch.steps import make_jitted_guarded_train_step

        self.model = model_config(cfg)
        self.tcfg = TrainConfig(**cell["train"])
        self.mix, self.seed = mix, seed
        self.vocab = cfg["token_vocab"]
        self.step_fn = make_jitted_guarded_train_step(self.model, self.tcfg)
        self.key = common.jax_key(seed)
        self.params = weights.make_params(self.model, self.key)
        self.opt = jax.jit(optim.init_state)(self.params)
        self.guard = optim.init_guard_state(cell.get("spike_window", 16))
        self.n = 0

    def batch(self, step: int) -> dict:
        return {"tokens": jnp.asarray(
            traffic.train_tokens(self.mix, self.vocab, self.seed, step))}

    def step(self):
        """One step; returns (loss, skipped) and keeps the clip statistic,
        the global gradient norm, in ``grad_norm``."""
        with TraceAnnotation("data"):
            feed = self.batch(self.n)
        with TraceAnnotation("step"):
            self.params, self.opt, self.guard, metrics = self.step_fn(
                self.params, self.opt, self.guard, feed)
        with TraceAnnotation("sync"):
            loss = float(metrics["loss"])
            self.grad_norm = float(metrics["grad_norm"])
            skipped = float(metrics["skipped"]) > 0.0
        self.n += 1
        return loss, skipped

    def free(self):
        self.params = self.opt = self.guard = self.step_fn = None
        gc.collect()


def gaps(prog: dict, ref: dict, keys=None) -> dict:
    """Each leaf's gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    keys = sorted(ref) if keys is None else keys
    med = float(np.median([ref[k] for k in keys]))
    return {k: abs(prog.get(k, np.nan) - ref[k]) / max(ref[k], med, 1e-30)
            for k in keys}


def worst(g: dict) -> tuple:
    """(gap, leaf) of the worst leaf; a leaf the program lacks is worst."""
    at = max(g, key=lambda k: np.inf if np.isnan(g[k]) else g[k])
    return g[at], at


def compare(readings: dict, ref: dict) -> dict:
    """The numbers compared, from the program's readings and the
    reference's: the first step's loss (later steps' losses also carry the
    amplified differences of the updates before them, and are reported,
    not compared), the first step's clip statistic (the global gradient
    norm), the clipped first gradient by the worst leaf and by the median
    leaf (every leaf carries the clip scale, so a wrong scale moves the
    median leaf by as much), and the change after the checked steps by
    the worst leaf. Leaves whose reference gradient is
    under a thousandth of the median leaf's move by round-off alone and
    are left out of the change."""
    loss = [abs(a - b) / abs(b) for a, b in zip(readings["loss"],
                                                ref["loss"])]
    g = gaps(readings["first_grad"], ref["first_grad"])
    g_gap, g_at = worst(g)
    med = float(np.median(list(ref["first_grad"].values())))
    moving = [k for k, v in ref["first_grad"].items() if v >= 1e-3 * med]
    c_gap, c_at = worst(gaps(readings["change"], ref["change"], moving))
    return {"loss_gap": loss[0],
            "norm_gap": abs(readings["grad_norm"] - ref["grad_norm"])
            / ref["grad_norm"],
            "grad_gap": g_gap,
            "grad_gap_median": float(np.median(list(g.values()))),
            "change_gap": c_gap,
            "_where": {"grad_gap": g_at, "change_gap": c_at,
                       "loss_gaps": loss}}


def reference_readings(cfg: dict, cell: dict, mix: dict, seed: int,
                       prec: str = "f32") -> dict:
    ref = reference.TrainReference(cfg["model"], common.jax_key(seed),
                                   cell["train"], prec)
    losses = []
    for s in range(CHECK_STEPS):
        losses.append(ref.step(
            traffic.train_tokens(mix, cfg["token_vocab"], seed, s)))
    out = {"loss": losses, "grad_norm": ref.grad_norms[0],
           "first_grad": dict(ref.first_grad), "change": ref.change()}
    del ref
    gc.collect()
    return out


def program_readings(prog: Program) -> dict:
    """Drive the program through the checked steps and read them: each
    loss, the first step's global gradient norm, the clipped first
    gradient from the optimizer's first moment after one step
    (m = (1 - b1) * clip * g), and each leaf's change."""
    losses = []
    for s in range(CHECK_STEPS):
        loss, _ = prog.step()
        losses.append(loss)
        if s == 0:
            norm = prog.grad_norm
            first = leaf_norms(prog.opt.m, 1.0 / (1.0 - prog.tcfg.b1))
    return {"loss": losses, "grad_norm": norm, "first_grad": first,
            "change": change_norms(prog.params, prog.key)}


def run(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float,
        tracer, t_start: float, devices) -> dict:
    from repro import reduce as R

    R.set_default_backend(cell["reduce_backend"])
    prog = Program(cell, cfg, mix, seed)
    readings = program_readings(prog)
    tokens_per_step = mix["batch"] * mix["seq"]
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    steps = skipped = 0
    ends, losses = [t0], []
    while True:
        loss, skip = prog.step()
        losses.append(loss)
        steps += 1
        skipped += skip
        ends.append(time.perf_counter())
        window_s = ends[-1] - t0
        if window_s >= seconds:
            break
    dt = np.diff(ends) * 1e3
    trace = None
    if tracer is not None:
        with tracer:
            for _ in range(cell["trace_steps"]):
                prog.step()
        trace = tracer.reduce()
    peak = common.memory_peak(devices)
    prog.free()
    ref = reference_readings(cfg, cell, mix, seed)
    numbers = compare(readings, ref)
    return {
        "attempted": steps,
        "failed": skipped,
        "counters": {
            "setup_s": setup_s, "window_s": window_s,
            "tokens": (steps - skipped) * tokens_per_step, "seq": mix["seq"],
        },
        "trace": trace,
        "memory_peak_bytes": peak,
        "numbers": numbers,
        "notes": {"step_ms_p50": float(np.median(dt)),
                  "step_ms_max": float(dt.max()),
                  "slowest_step": int(dt.argmax()),
                  "steps_over_1.5x_p50": int((dt > 1.5 * np.median(dt)).sum()),
                  "skipped_steps": skipped,
                  "loss_first": losses[0], "loss_last": losses[-1]},
    }
