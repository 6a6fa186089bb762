"""Training driver: config -> data -> jitted step -> checkpointed loop.

Runs anywhere: on this CPU container it trains the --tiny configs end to
end (examples/quickstart.py drives it); on a TPU fleet the same entry point
takes --arch <full> and the production mesh.

  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --tiny \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro import reduce as R
from repro.checkpoint import CheckpointManager
from repro.configs import TrainConfig, get_arch
from repro.data import Prefetcher, ShardInfo, SyntheticLM
from repro.launch.device import print_device_report, use_compile_cache
from repro.launch.mesh import make_data_mesh, replicated
from repro.launch.steps import (
    make_jitted_guarded_train_step,
    make_jitted_train_step,
    make_mesh_guarded_train_step,
)
from repro.models import init_params
from repro.models.frontends import synth_image_embeds
from repro.runtime import (
    ChaosMonkey,
    GuardMetrics,
    PreemptionGuard,
    StepGuard,
    TrainSupervisor,
)


def build(cfg, tcfg, batch: int, seq: int, mesh=None, *, guard=False,
          spike_z: float = 6.0, data_mesh=None):
    key = jax.random.PRNGKey(tcfg.seed)

    def init_opt(p):
        return optim.init_state(
            p, fused_second_moment=tcfg.fused_second_moment
        )

    if data_mesh is None:
        params, _ = init_params(key, cfg)
        opt_state = init_opt(params)
    else:
        # born replicated on every mesh device: no single-device copy to
        # spread (it would double device 0's share), and the step sees one
        # input placement from its first call, so it compiles once
        rep = replicated(data_mesh)
        params = jax.jit(
            lambda k: init_params(k, cfg)[0], out_shardings=rep
        )(key)
        opt_state = jax.jit(init_opt, out_shardings=rep)(params)
    # donate_argnums: params and opt_state update IN PLACE (their buffers
    # are reused for the outputs) -- callers rebind both from the return
    if data_mesh is not None:
        step_fn = make_mesh_guarded_train_step(cfg, tcfg, data_mesh,
                                               spike_z=spike_z)
    elif guard:
        step_fn = make_jitted_guarded_train_step(cfg, tcfg, mesh,
                                                 spike_z=spike_z)
    else:
        step_fn = make_jitted_train_step(cfg, tcfg, mesh)
    return params, opt_state, step_fn


def main(argv=None):
    """Train from the command line; returns one record per taken step
    (``_step_record``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument(
        "--fused-second-moment",
        action="store_true",
        help="olmax-style scalar v EMA fed by the norm launch's per-leaf "
        "sumsq slots (one HBM trip per grad leaf per step)",
    )
    ap.add_argument(
        "--guard",
        action="store_true",
        help="guarded step: the clip statistic's launch also counts NaN/Inf "
        "grad elements (in-launch census); a poisoned or loss-spiking step "
        "passes params/opt state through bitwise unchanged, and "
        "--max-bad-steps consecutive skips roll back to the last committed "
        "checkpoint (requires --ckpt-dir for rollback)",
    )
    ap.add_argument(
        "--spike-window", type=int, default=16,
        help="guarded step: accepted-loss window length for the "
        "median/MAD loss-spike detector",
    )
    ap.add_argument(
        "--spike-z", type=float, default=6.0,
        help="guarded step: robust z-score above the window median that "
        "forces a skip",
    )
    ap.add_argument(
        "--max-bad-steps", type=int, default=3,
        help="guarded step: consecutive skipped steps before rollback",
    )
    ap.add_argument(
        "--mesh", nargs="?", type=int, const=0, default=None,
        metavar="DEVICES",
        help="mesh-aware guard: data-parallel guarded step over DEVICES "
        "devices (every visible device when omitted) under shard_map with "
        "the deterministic fixed-order gradient combine, so the "
        "skip/rollback decisions are bit-identical on every replica "
        "(requires --guard; --batch must divide the device count)",
    )
    ap.add_argument(
        "--chaos", type=float, default=0.0,
        help="deterministic fault-injection drill: per-step probability of "
        "an injected fault (half NaN-poisoned grads, half transient step "
        "failure), scheduled by --chaos-seed (requires --guard)",
    )
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for the --chaos schedule (same seed = same "
                    "faults, on every host and every rerun)")
    ap.add_argument(
        "--chaos-host", type=int, default=0,
        help="with --mesh, the shard/host index whose LOCAL grads the NaN "
        "injection poisons -- the cross-device census must still skip "
        "every host in lockstep",
    )
    ap.add_argument(
        "--status-path", default=None,
        help="guard-metrics JSON status file, rewritten atomically at every "
        "checkpoint commit (default: <ckpt-dir>/guard_status.json)",
    )
    ap.add_argument(
        "--reduce-backend",
        default=None,
        choices=R.available_backends() + ("auto",),
        help="process-wide repro.reduce backend (default: cost-model auto)",
    )
    args = ap.parse_args(argv)

    use_compile_cache()
    print_device_report()
    if args.mesh is not None and not args.guard:
        ap.error("--mesh requires --guard")
    if args.chaos and not args.guard:
        ap.error("--chaos requires --guard")
    if args.reduce_backend:
        R.set_default_backend(args.reduce_backend)
    data_mesh = None
    if args.mesh is not None:
        data_mesh = make_data_mesh(args.mesh or None)
        world = int(data_mesh.devices.size)
        if args.batch % world:
            ap.error(f"--batch {args.batch} must divide {world} devices")
        print(f"mesh guard: {world}-way data mesh, deterministic combine")
    cfg = get_arch(args.arch, tiny=args.tiny)
    tcfg = TrainConfig(
        learning_rate=args.lr, total_steps=args.steps,
        warmup_steps=max(1, args.steps // 10), microbatches=args.microbatches,
        fused_second_moment=args.fused_second_moment,
    )
    params, opt_state, step_fn = build(
        cfg, tcfg, args.batch, args.seq, guard=args.guard,
        spike_z=args.spike_z, data_mesh=data_mesh,
    )
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M steps={args.steps}")

    data = SyntheticLM(
        cfg.vocab_size, args.seq, args.batch, ShardInfo(), seed=tcfg.seed,
        n_codebooks=cfg.n_codebooks,
    )
    # Guarded mode reads `data` directly: a rollback rewinds `data.seek`,
    # which a double-buffered prefetch queue would make inexact (batches
    # already queued under the old position would still be served).
    prefetch = None if args.guard else Prefetcher(data)
    ctx = (
        synth_image_embeds(
            jax.random.PRNGKey(1), args.batch, cfg.n_img_tokens, cfg.d_model,
            jnp.dtype(cfg.dtype),
        )
        if cfg.n_img_tokens
        else None
    )

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    guard = PreemptionGuard()

    def fresh_guard_state():
        g = optim.init_guard_state(args.spike_window)
        return g if data_mesh is None else jax.device_put(
            g, replicated(data_mesh)
        )

    guard_state = fresh_guard_state() if args.guard else None
    step_guard = StepGuard(args.max_bad_steps) if args.guard else None
    chaos = None
    if args.chaos > 0:
        chaos = ChaosMonkey.from_seed(
            args.chaos_seed, n_steps=args.steps,
            nan_rate=args.chaos / 2, fail_rate=args.chaos / 2,
            host=args.chaos_host,
        )
        print(f"chaos: seed={args.chaos_seed} rate={args.chaos} "
              f"nan_steps={sorted(chaos.nan_steps)} "
              f"fail_steps={sorted(chaos.fail_steps)}")
    gmetrics = GuardMetrics() if args.guard else None
    status_path = args.status_path
    if status_path is None and args.ckpt_dir:
        status_path = os.path.join(args.ckpt_dir, "guard_status.json")
    start_step = 0
    if ckpt and ckpt.latest() is not None:
        ckpt.wait()  # drain any mid-flush save from a prior incarnation
        step0 = ckpt.latest()
        params, opt_state = ckpt.restore(step0, (params, opt_state))
        data.seek(ckpt.manifest(step0)["extra"]["data_step"])
        start_step = step0
        print(f"resumed from step {step0}")
    if args.guard and ckpt and ckpt.latest() is None:
        # anchor commit so a guard trip before the first periodic save
        # still has a rollback target
        ckpt.save(0, (params, opt_state),
                  extra={"data_step": data.state()["step"]})

    history = []
    t0 = time.time()
    step = start_step
    while step < args.steps:
        batch = data.next() if prefetch is None else prefetch.next()
        feed = {"tokens": jnp.asarray(batch["tokens"])}
        if ctx is not None:
            feed["image_embeds"] = ctx
        if chaos is not None:
            # keyed on step+1 so the schedule names the step being taken;
            # fire-once semantics keep post-rollback replays clean
            if data_mesh is not None:
                world = int(data_mesh.devices.size)
                feed["chaos_scale"] = chaos.corrupt_shard(
                    jnp.ones((world,), jnp.float32), step + 1, shards=world
                )
            else:
                feed["chaos_scale"] = chaos.corrupt(
                    jnp.ones((1,), jnp.float32), step + 1
                )

        def attempt():
            if chaos is not None:
                chaos.on_step(step + 1, guard)
            if args.guard:
                return step_fn(params, opt_state, guard_state, feed)
            return step_fn(params, opt_state, feed)

        if step_guard is not None:
            failures_before = step_guard.transient_failures
            out = step_guard.retry(attempt)
            if gmetrics is not None:
                gmetrics.record_retry(
                    step_guard.transient_failures - failures_before
                )
        else:
            out = attempt()
        if args.guard:
            params, opt_state, guard_state, metrics = out
        else:
            params, opt_state, metrics = out
        step += 1
        history.append(_step_record(step, metrics, params))
        if step % args.log_every == 0:
            dt = (time.time() - t0) / args.log_every
            extra = ""
            if args.guard:
                extra = (
                    f" nonfinite {float(metrics['nonfinite']):.0f}"
                    f" skips {int(guard_state.skipped)}"
                )
            print(
                f"step {step:5d} loss {history[-1]['loss']:.4f} "
                f"gnorm {float(metrics['grad_norm']):.3f} "
                f"lr {float(metrics['lr']):.2e} {dt*1e3:.0f} ms/step"
                + extra
            )
            t0 = time.time()
        skipped = False
        if step_guard is not None:
            skipped = history[-1]["skipped"]
            step_guard.record(skipped)
            if gmetrics is not None:
                gmetrics.record_step(
                    step, skipped=skipped,
                    census_total=float(metrics.get("nonfinite", 0.0)),
                )
            if step_guard.should_rollback():
                if ckpt is None:
                    print("guard: rollback wanted but no --ckpt-dir; "
                          "resetting the bad-step counter only")
                    step_guard.reset()
                else:
                    ckpt.wait()
                    back = ckpt.latest()
                    params, opt_state = ckpt.restore(
                        back, (params, opt_state)
                    )
                    data.seek(ckpt.manifest(back)["extra"]["data_step"])
                    guard_state = fresh_guard_state()
                    step_guard.reset()
                    step_guard.rollbacks += 1
                    if gmetrics is not None:
                        gmetrics.record_rollback()
                        if status_path:
                            gmetrics.write(status_path)
                    step = back
                    print(f"guard: rolled back to step {back}")
                continue
        # never commit mid-skip-streak (see TrainSupervisor.run)
        if ckpt and ((step % args.ckpt_every == 0 and not skipped)
                     or guard.should_stop):
            ckpt.save(step, (params, opt_state),
                      extra={"data_step": data.state()["step"]})
            if gmetrics is not None:
                gmetrics.record_commit()
                if status_path:
                    gmetrics.write(status_path)
                snap = gmetrics.snapshot()
                print(
                    f"commit step {step}: skipped "
                    f"{snap['steps_skipped']}/{snap['steps_total']} "
                    f"retries {snap['retries']} "
                    f"rollbacks {snap['rollbacks']}"
                )
        if guard.should_stop:
            print("preempted: checkpoint flushed, exiting cleanly")
            break
    if ckpt:
        ckpt.wait()
    if prefetch is not None:
        prefetch.close()
    print(f"final loss {history[-1]['loss']:.4f} "
          f"(first {history[0]['loss']:.4f})")
    return history


def _step_record(step: int, metrics: dict, params) -> dict:
    """One taken step as plain Python numbers: what ``main`` returns per
    step. ``param_devices`` is the fewest devices any parameter leaf is
    placed on -- a data-parallel step keeps a replica on every mesh
    device."""
    return {
        "step": step,
        "loss": float(metrics["loss"]),
        "grad_norm": float(metrics["grad_norm"]),
        "skipped": float(metrics.get("skipped", 0.0)) > 0.0,
        "nonfinite": float(metrics.get("nonfinite", 0.0)),
        "param_devices": min(
            len(p.sharding.device_set) for p in jax.tree.leaves(params)
        ),
    }


if __name__ == "__main__":
    main()
