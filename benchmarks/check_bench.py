"""CI gate over ``BENCH_reduce.json``: structure, launch counts, MMA totals,
HBM traffic, and the zero-copy staging-free property.

``benchmarks/run.py --json`` mirrors every bench row into a machine-readable
report; this checker turns the perf invariants the engine advertises into
build failures instead of silent drift:

  1. LAUNCH COUNT -- one ``reduce_many`` batch (and the whole-pytree
     ``reduce_tree`` statistic) lowers to EXACTLY one ``pallas_call`` on the
     Pallas backends, including with ``num_cores > 1`` (the striped grid must
     never fall back to one launch per lane, per segment, or per part).
  2. MMA TOTALS -- the trace-counted MMA rows the kernel bench emits
     (``mma_fused_262k_c{c}``) match ``cost_model.fused_mma_ops``:
     n/(m^2 c) + c per lane. A mismatch means the kernel geometry and the
     cost model (which the planner trusts) have diverged.
  3. STAGING-FREE INGESTION -- lowering ``reduce`` / ``reduce_many`` on bf16
     inputs for both Pallas backends produces NO n-sized
     ``convert_element_type``, ``pad``, or ``concatenate`` outside the
     pallas_call (``repro.reduce.inspect.assert_staging_free``): the kernels
     read the caller's buffer directly, in its native dtype. The NORM path
     (sumsq / norm2 / moments, incl. ``reduce_tree``'s clipping statistic)
     additionally forbids n-sized ``mul``/``integer_pow``/``sign`` outside
     the kernel: the elementwise prologue runs IN-kernel, so the whole
     norm is single-stream (one read of the raw leaf, one launch).
  4. HBM BYTES -- the ``hbm_*`` rows the kernel bench emits match
     ``cost_model.hbm_bytes`` for the plan they ran, the zero-copy bf16
     model stays at n*2 + O(c m^2), and the launch-boundary bytes of the
     lowered program (``inspect.pallas_io_bytes``) equal the model's
     ``launch_io`` -- traffic asserted against the traced geometry, not
     just claimed.
  5. ONE-TRIP OPTIMIZER STEP -- the clipped-AdamW statistic
     (``optim.global_norm_and_clip``) is epilogue-free on the Pallas
     backends (NO sqrt/rsqrt/div/min/max eqns of any size outside the
     pallas_call: the norm's sqrt and the clip coefficient finish
     in-launch), a jitted ``apply_updates`` lowers to exactly one
     reduction launch, the launch moves <= 1.25x the raw grad bytes
     (== the parts model with the fork's output slots), and the
     fused-second-moment update keeps its elementwise pass free of
     n-sized sqrt/div/min (the ``hbm_step_grads_*`` rows witness the
     byte claim in the artifact).
  6. GUARDED STEP CENSUS -- the clip statistic with the in-launch
     non-finite census is still one pallas_call, adds f32 OUTPUT slots
     only (kernel reads byte-identical to the unguarded model), and the
     whole jitted guarded update (bitwise skip + spike detector) lowers
     with no ``is_finite``/``select_n`` outside the kernel.
  7. SERVE GUARD -- the census-guarded DECODE statistic
     (``runtime.serving.guarded_logit_stat``, one segmented launch over
     the slots' rows, and the single-array ``reduce(..., census=True)``)
     is one pallas_call on both Pallas backends, census-free in lowering,
     and reads exactly the bytes the unguarded statistic reads
     (``--serve`` runs it standalone).
  8. MMA SCAN -- the triangular-MMA prefix sum is one pallas_call at
     every lane count, staging-free on bf16 ingest (incl. reverse /
     exclusive), its trace-counted MMA rows (``mma_scan_262k_c{c}``)
     match ``cost_model.scan_mma_ops`` including the lane/carry split,
     and the lowered launch-boundary bytes equal
     ``cost_model.scan_hbm_bytes``'s ``launch_io`` -- with the bf16
     single-stream row beating the staged two-pass model by >4x.

Run as ``python -m benchmarks.check_bench BENCH_reduce.json``.
"""

from __future__ import annotations

import json
import sys

import jax.numpy as jnp


def check_report(path: str) -> None:
    """Checks over the JSON mirror (structure + model recomputation)."""
    with open(path) as f:
        d = json.load(f)
    assert d["sections"], "no bench sections ran"
    rows = [r for s in d["sections"] for r in s["rows"]]
    assert rows, "bench produced no rows"
    bad = [r for r in rows if str(r["name"]).startswith("bench_error")]
    assert not bad, f"bench sections errored: {bad}"
    assert any("reduce_many" in str(r["name"]) for r in rows), rows
    # trace-counted MMA totals must match the cost model the planner trusts
    from repro.core import cost_model

    mma_rows = {
        r["name"]: r for r in rows if str(r["name"]).startswith("mma_fused_")
    }
    assert mma_rows, "kernel bench no longer emits mma_fused_* trace rows"
    for name, row in mma_rows.items():
        c = int(name.rsplit("_c", 1)[1])
        # problem size and block depth of the plan the bench actually ran
        # travel in the derived column -- never assumed here
        kv = dict(p.split("=", 1) for p in str(row["derived"]).split(";"))
        want = cost_model.fused_mma_ops(
            int(kv["n"]), num_cores=c, tiles_per_block=int(kv["tpb"])
        ).total
        got = int(row["value"])
        assert got == want, (
            f"{name}: traced {got} MMAs but cost model says {want} -- kernel "
            "geometry and cost_model.fused_mma_ops have diverged"
        )
    # the scan kernel's trace rows against the triangular-scan cost model:
    # total AND the lane/carry split (the carry-rebuild overhead is the
    # Dakkak trade the planner reasons about, so its drift is a failure too)
    scan_rows = {
        r["name"]: r for r in rows if str(r["name"]).startswith("mma_scan_")
    }
    assert scan_rows, "kernel bench no longer emits mma_scan_* trace rows"
    for name, row in scan_rows.items():
        c = int(name.rsplit("_c", 1)[1])
        kv = dict(p.split("=", 1) for p in str(row["derived"]).split(";"))
        want = cost_model.scan_mma_ops(
            int(kv["n"]), num_cores=c, tiles_per_block=int(kv["tpb"])
        )
        got = int(row["value"])
        assert got == want.total, (
            f"{name}: traced {got} MMAs but cost model says {want.total} -- "
            "scan kernel geometry and cost_model.scan_mma_ops have diverged"
        )
        assert int(kv["lane"]) == want.lane_scan, (name, kv, want)
        assert int(kv["carry"]) == want.carry_worst, (name, kv, want)
    check_hbm_rows(rows)


def check_hbm_rows(rows) -> None:
    """The hbm_* traffic rows: recompute the model from each row's derived
    params and require the zero-copy bf16 win over the staged-f32 path."""
    from repro.core import cost_model

    hbm = {r["name"]: r for r in rows if str(r["name"]).startswith("hbm_")}
    assert hbm, "kernel bench no longer emits hbm_* traffic rows"
    modeled = {}
    for name, row in hbm.items():
        kv = dict(p.split("=", 1) for p in str(row["derived"]).split(";"))
        want = cost_model.hbm_bytes(
            kv["path"],
            int(kv["n"]),
            int(kv["itemsize"]),
            num_cores=int(kv.get("c", 1)),
            tiles_per_block=int(kv.get("tpb", 8)),
            segments=int(kv.get("segments", 1)),
        )
        got = int(row["value"])
        assert got == want.total, (
            f"{name}: bench emitted {got} modeled HBM bytes but "
            f"cost_model.hbm_bytes says {want.total}"
        )
        if "measured" in kv:  # launch-boundary bytes of the lowered program
            assert int(kv["measured"]) == want.launch_io, (
                f"{name}: lowered pallas_call moves {kv['measured']} bytes "
                f"but the model's launch_io is {want.launch_io} -- kernel "
                "operands and the traffic model have diverged"
            )
        # keyed by ROW NAME: the sumsq row intentionally reuses path=fused
        # (the single-stream identity), so a (path, itemsize) key would let
        # one row silently shadow the other
        modeled[str(name)] = want.total

    def _row(prefix):
        matches = [v for k, v in modeled.items() if k.startswith(prefix)]
        assert matches, f"kernel bench no longer emits the {prefix}* row"
        return matches[0]

    # the whole point, as an inequality the artifact must witness:
    # zero-copy bf16 ingestion moves < half the staged-f32 bytes
    n2 = _row("hbm_fused_262k_bf16")
    staged = _row("hbm_fused_staged_262k_bf16")
    assert n2 * 2 < staged, (n2, staged)
    # single-stream norms: the in-kernel square prologue makes bf16 sumsq
    # byte-identical to the plain sum and >4x cheaper than the PR-4
    # two-pass route (host square + staged f32 stream)
    sumsq = _row("hbm_sumsq_262k_bf16")
    staged_sq = _row("hbm_sumsq_staged_262k_bf16")
    assert sumsq * 4 < staged_sq, (sumsq, staged_sq)
    # the scan analogue: a bf16 prefix sum streams AND writes at native
    # width in one launch, >4x cheaper than the XLA two-pass f32 route
    # (upcast copy + f32 scan + downcast) it replaced
    scan_zc = _row("hbm_scan_262k_bf16")
    scan_staged = _row("hbm_scan_staged_262k_bf16")
    assert scan_zc * 4 < scan_staged, (scan_zc, scan_staged)
    _row("hbm_tree_norm2")  # the optimizer-statistic row must exist
    # the one-HBM-trip step: for both dtypes, the whole statistic side of an
    # optimizer step (per-leaf sumsq + gnorm + clip, one launch) stays
    # within 25% of the raw grad bytes -- i.e. one trip, not two -- and
    # beats the modeled two-trip route it replaced
    for dt_name in ("bf16", "f32"):
        row = hbm[f"hbm_step_grads_{dt_name}"]
        kv = dict(p.split("=", 1) for p in str(row["derived"]).split(";"))
        grad_bytes = int(kv["n"]) * int(kv["itemsize"])
        got = int(row["value"])
        assert got <= 1.25 * grad_bytes, (
            f"hbm_step_grads_{dt_name}: modeled step statistic moves {got} "
            f"bytes for {grad_bytes} grad bytes -- the one-trip property "
            "drifted"
        )
        assert got < _row(f"hbm_step_grads_2trip_{dt_name}")


def check_launch_counts() -> None:
    """The 1-launch property, asserted on the lowered jaxprs (cheap: no
    execution, trace only -- safe on the CI CPU)."""
    from repro import reduce as R
    from repro.optim import adamw
    from repro.reduce import inspect as rinspect

    arrs = [jnp.ones((300,)), jnp.ones((4, 65)), jnp.ones(())]
    tree = {"w": jnp.ones((4, 256)), "b": [jnp.ones((300,)), jnp.ones(())]}
    for backend in ("pallas_fused", "pallas_hier"):
        for c in (1, 2):
            n = rinspect.count_pallas_calls(
                lambda a, b=backend, c=c: R.reduce_many(a, backend=b, num_cores=c),
                arrs,
            )
            assert n == 1, f"reduce_many[{backend}, c={c}]: {n} pallas_calls"
            n = rinspect.count_pallas_calls(
                lambda g, b=backend, c=c: R.reduce_tree(
                    g, "norm2", backend=b, num_cores=c
                ),
                tree,
            )
            assert n == 1, f"reduce_tree[{backend}, c={c}]: {n} pallas_calls"
    # and the optimizer-facing entry point rides the same single launch
    n = rinspect.count_pallas_calls(
        lambda g: adamw.global_norm(g, backend="pallas_fused"), tree
    )
    assert n == 1, "global_norm launch count drifted"
    # the prologue kinds stay single-launch on the fused backend: the
    # square / dual-accumulator maps run INSIDE the one kernel
    x = jnp.ones((300_000,), jnp.bfloat16)
    for kind in ("sumsq", "norm2", "moments"):
        n = rinspect.count_pallas_calls(
            lambda v, k=kind: R.reduce(v, kind=k, backend="pallas_fused"), x
        )
        assert n == 1, f"reduce[{kind}, pallas_fused]: {n} pallas_calls"
    for backend in ("pallas_fused", "pallas_hier"):
        n = rinspect.count_pallas_calls(
            lambda g, b=backend: R.reduce_tree(g, "norm2", backend=b), tree
        )
        assert n == 1, f"reduce_tree norm2[{backend}]: {n} pallas_calls"


def check_staging_free() -> None:
    """Zero-copy proven on the lowered jaxpr: reducing a bf16 stream on the
    Pallas backends must not cast, pad, or concatenate anything stream-sized
    outside the pallas_call (trace only -- safe on the CI CPU). The norm
    path additionally forbids n-sized mul/pow/sign OUTSIDE the kernel --
    the host-side square pass the in-kernel prologues removed."""
    from repro import reduce as R
    from repro.reduce import inspect as rinspect

    x = jnp.zeros((300_000,), jnp.bfloat16)  # ragged: tail-masked in-kernel
    arrs = [jnp.zeros((s,), jnp.bfloat16) for s in (70_000, 33, 20_000)]
    tree = {
        "w": jnp.zeros((40, 256), jnp.bfloat16),
        "b": [jnp.zeros((3000,), jnp.bfloat16), jnp.zeros((), jnp.bfloat16)],
    }
    for backend in ("pallas_fused", "pallas_hier"):
        rinspect.assert_staging_free(
            lambda v, b=backend: R.reduce(v, backend=b), x
        )
        rinspect.assert_staging_free(
            lambda a, b=backend: R.reduce_many(a, backend=b), arrs
        )
        # single-stream norms: sumsq / norm2 / moments square in-kernel
        for kind in ("sumsq", "norm2", "moments"):
            rinspect.assert_staging_free(
                lambda v, b=backend, k=kind: R.reduce(v, kind=k, backend=b),
                x,
                extra_primitives=rinspect.PROLOGUE_PRIMITIVES,
            )
            rinspect.assert_staging_free(
                lambda a, b=backend, k=kind: R.reduce_many(
                    a, kind=k, backend=b
                ),
                arrs,
                extra_primitives=rinspect.PROLOGUE_PRIMITIVES,
            )
        rinspect.assert_staging_free(
            lambda g, b=backend: R.reduce_tree(g, "norm2", backend=b),
            tree,
            extra_primitives=rinspect.PROLOGUE_PRIMITIVES,
        )
    # (gradients are exempt by design: the VJP's cotangent broadcast-and-
    # cast IS the n-sized output being produced, not ingestion staging.)


def check_optimizer_step() -> None:
    """The one-HBM-trip optimizer step, gated on lowered jaxprs (trace only
    -- safe on the CI CPU):

      a. the clip statistic is EPILOGUE-FREE on the Pallas backends: no
         sqrt/rsqrt/div/min/max eqns of ANY size outside the pallas_call --
         the norm's sqrt and the clip coefficient's min/max/div finish
         inside the launch (``inspect.assert_epilogue_free``; scalar eqns
         are invisible to the n-sized staging walker, hence the dedicated
         any-size check);
      b. a jitted AdamW update lowers to EXACTLY one reduction launch
         (standard and fused second moment alike);
      c. the launch moves at most 1.25x the raw grad bytes (measured
         pallas_call boundary bytes == the parts model with the fork's +2
         output slots);
      d. the fused-second-moment update has NO n-sized sqrt/div/min outside
         the kernel: the scalar nu EMA carries the sqrt/divide, so the
         elementwise pass is mul/add only (the standard variant keeps its
         elementwise v sqrt -- only the fused path advertises this).
    """
    import jax

    from repro import optim
    from repro.configs import TrainConfig
    from repro.optim import adamw
    from repro.reduce import inspect as rinspect

    tree = {
        "w": jnp.ones((40, 256), jnp.bfloat16),
        "b": [jnp.ones((3000,), jnp.bfloat16), jnp.ones((), jnp.bfloat16)],
    }
    for backend in ("pallas_fused", "pallas_hier"):
        stat = lambda g, b=backend: adamw.global_norm_and_clip(
            g, 1.0, backend=b, return_per_leaf=True
        )
        rinspect.assert_epilogue_free(stat, tree)  # (a)
        n = rinspect.count_pallas_calls(stat, tree)
        assert n == 1, f"global_norm_and_clip[{backend}]: {n} pallas_calls"
        grad_bytes = sum(v.nbytes for v in jax.tree.leaves(tree))
        measured = rinspect.pallas_io_bytes(jax.make_jaxpr(stat)(tree))
        assert measured <= 1.25 * grad_bytes, (backend, measured, grad_bytes)  # (c)
        from repro.core import cost_model

        nleaves = len(jax.tree.leaves(tree))
        want = cost_model.hbm_bytes(
            "parts", grad_bytes // 2, 2, segments=nleaves + 2
        )
        assert measured == want.launch_io, (backend, measured, want)

    # (b) + (d): the full update step. f32 params/grads keep the jaxpr free
    # of the legitimate mixed-precision casts so the walker sees only the
    # update math itself.
    tcfg = TrainConfig()
    params = {"w": jnp.ones((40, 256)), "b": jnp.ones((3000,))}
    grads = jax.tree.map(jnp.ones_like, params)
    for fused in (False, True):
        state = optim.init_state(params, fused_second_moment=fused)
        step = lambda p, g, s, f=fused: optim.apply_updates(
            p, g, s, tcfg, reduce_backend="pallas_fused",
            fused_second_moment=f,
        )
        n = rinspect.count_pallas_calls(step, params, grads, state)
        assert n == 1, f"apply_updates[fused={fused}]: {n} pallas_calls"
        if fused:
            # EPILOGUE_PRIMITIVES only: the update legitimately multiplies
            # n-sized (m EMA, the scalar-coefficient apply), so the
            # PROLOGUE mul gate does not apply here -- the claim is no
            # n-sized sqrt/div/min pass, the elementwise math the scalar
            # nu reciprocal replaced
            rinspect.assert_staging_free(
                step, params, grads, state,
                extra_primitives=rinspect.EPILOGUE_PRIMITIVES,
            )


def check_guarded_step() -> None:
    """The guarded step costs NOTHING extra on the input side, gated on
    lowered jaxprs (trace only -- safe on the CI CPU):

      a. the clip statistic WITH the non-finite census is still exactly one
         pallas_call on both Pallas backends -- the 0/1 isfinite mask rides
         the same MMA tiles, it is not a second reduction;
      b. measured launch-boundary bytes == the parts model widened by the
         census slots (``census=nleaves+1``: per-leaf counts + total), and
         the KERNEL-READ side is byte-identical to the unguarded model --
         census adds f32 OUTPUT slots only, zero extra HBM input bytes;
      c. the whole jitted guarded update (census + bitwise skip + spike
         detector) lowers with NO ``is_finite``/``select_n`` of any size
         outside the pallas_call (``inspect.assert_census_free``, strict
         ``min_elems=1``) and still exactly one reduction launch: the
         skip is a bitwise blend, not a branch.
    """
    import jax

    from repro import optim
    from repro.configs import TrainConfig
    from repro.core import cost_model
    from repro.optim import adamw
    from repro.reduce import inspect as rinspect

    tree = {
        "w": jnp.ones((40, 256), jnp.bfloat16),
        "b": [jnp.ones((3000,), jnp.bfloat16), jnp.ones((), jnp.bfloat16)],
    }
    grad_bytes = sum(v.nbytes for v in jax.tree.leaves(tree))
    nleaves = len(jax.tree.leaves(tree))
    plain = cost_model.hbm_bytes("parts", grad_bytes // 2, 2,
                                 segments=nleaves + 2)
    want = cost_model.hbm_bytes("parts", grad_bytes // 2, 2,
                                segments=nleaves + 2, census=nleaves + 1)
    assert want.kernel_read == plain.kernel_read, (want, plain)  # (b) input
    for backend in ("pallas_fused", "pallas_hier"):
        stat = lambda g, b=backend: adamw.global_norm_and_clip(
            g, 1.0, backend=b, return_per_leaf=True, census=True
        )
        n = rinspect.count_pallas_calls(stat, tree)
        assert n == 1, f"census stat[{backend}]: {n} pallas_calls"  # (a)
        measured = rinspect.pallas_io_bytes(jax.make_jaxpr(stat)(tree))
        assert measured == want.launch_io, (backend, measured, want)  # (b)

    # (c): the full guarded update -- f32 params/grads as in
    # check_optimizer_step so the walker sees only the update math
    tcfg = TrainConfig()
    params = {"w": jnp.ones((40, 256)), "b": jnp.ones((3000,))}
    grads = jax.tree.map(jnp.ones_like, params)
    state = optim.init_state(params)
    guard = optim.init_guard_state(8)
    loss = jnp.zeros((), jnp.float32)

    def gstep(p, g, s, gu, lo):
        return optim.guarded_apply_updates(
            p, g, s, tcfg, loss=lo, guard=gu, reduce_backend="pallas_fused"
        )

    rinspect.assert_census_free(gstep, params, grads, state, guard, loss)
    n = rinspect.count_pallas_calls(gstep, params, grads, state, guard, loss)
    assert n == 1, f"guarded_apply_updates: {n} pallas_calls"


def check_serve_guard() -> None:
    """The census-guarded DECODE statistic costs nothing extra on the input
    side, gated on lowered jaxprs (trace only -- safe on the CI CPU):

      a. ``runtime.serving.guarded_logit_stat`` (per-slot sumsq + per-slot
         non-finite census over one decode step's logits) is EXACTLY one
         pallas_call on both Pallas backends -- the per-slot statistic and
         the census ride one segmented launch over the slots' rows;
      b. the lowering is census-free: NO ``is_finite``/``select_n`` of any
         size outside the pallas_call (the guard is the in-kernel second
         accumulator, not a host-side mask pass);
      c. measured launch-boundary bytes == the segmented model widened by
         the census slots, and the KERNEL-READ side is byte-identical to
         the UNGUARDED row statistic's lowering (the same segmented launch
         without the census) -- the guard adds its per-slot f32 count
         OUTPUT slots only, zero extra kernel input bytes;
      d. the single-array form ``reduce(x, census=True)`` holds the same
         three properties (one launch, census-free, read-identical).
    """
    import jax

    from repro import reduce as R
    from repro.core import cost_model
    from repro.reduce import inspect as rinspect
    from repro.runtime.serving import guarded_logit_stat

    # one whole m^2 tile a slot: the cover fetches every byte once, so the
    # lowered operand bytes equal the model's fetched bytes exactly
    slots, vocab = 4, 16384
    logits = jnp.ones((slots, 1, vocab), jnp.float32)
    n = logits.size
    offsets = tuple(range(0, n + 1, vocab))
    plain = cost_model.hbm_bytes("segmented", n, 4, segments=slots,
                                 tiles=slots)
    want = cost_model.hbm_bytes("segmented", n, 4, segments=slots,
                                census=slots, tiles=slots)
    assert want.kernel_read == plain.kernel_read, (want, plain)
    for backend in ("pallas_fused", "pallas_hier"):
        guarded = lambda lg, b=backend: guarded_logit_stat(lg, backend=b)
        plan = R.plan_for((n,), jnp.float32, kind="sumsq", backend=backend,
                          segments=slots)
        unguarded = lambda lg, b=backend, p=plan: R.get_backend(
            b).sum_segments(lg.reshape(-1), offsets, p, "square")
        nc = rinspect.count_pallas_calls(guarded, logits)
        assert nc == 1, f"guarded decode stat[{backend}]: {nc} pallas_calls"
        rinspect.assert_census_free(guarded, logits)  # (b)
        measured = rinspect.pallas_io_bytes(jax.make_jaxpr(guarded)(logits))
        assert measured == want.launch_io, (backend, measured, want)  # (c)
        base = rinspect.pallas_io_bytes(jax.make_jaxpr(unguarded)(logits))
        # the guard's whole cost: one f32 census OUTPUT slot per slot
        assert measured - base == slots * 4, (backend, measured, base)
        assert want.kernel_read == plain.kernel_read  # reads identical

        # (d) the single-array serving guard: reduce(x, census=True). Its
        # baseline is the same parts-kernel lowering WITHOUT the census
        # (reduce_tree's one-leaf fork) -- the plain reduce() rides the
        # single-operand kernel whose block padding differs by design.
        x = jnp.ones((16384,), jnp.bfloat16)
        one = lambda v, b=backend: R.reduce(v, kind="sumsq", census=True,
                                            backend=b)
        nc = rinspect.count_pallas_calls(one, x)
        assert nc == 1, f"reduce census[{backend}]: {nc} pallas_calls"
        rinspect.assert_census_free(one, x)
        m1 = rinspect.pallas_io_bytes(jax.make_jaxpr(one)(x))
        m0 = rinspect.pallas_io_bytes(
            jax.make_jaxpr(
                lambda v, b=backend: R.reduce_tree(
                    [v], "sumsq", backend=b, return_per_leaf=True
                )
            )(x)
        )
        # the guard's whole cost: 2 census slots (part count + total)
        assert m1 - m0 == 2 * 4, (backend, m1, m0)
        want1 = cost_model.hbm_bytes("parts", x.size, 2, segments=2,
                                     census=2)
        plain1 = cost_model.hbm_bytes("parts", x.size, 2, segments=2)
        assert m1 == want1.launch_io, (backend, m1, want1)
        assert want1.kernel_read == plain1.kernel_read, (want1, plain1)
    print(
        "check_bench --serve: OK (guarded decode stat = 1 launch, "
        "census-free lowering, kernel reads byte-identical to unguarded)"
    )


def check_scan() -> None:
    """The triangular-MMA scan's perf contract, gated on lowered jaxprs
    (trace only -- safe on the CI CPU):

      a. a 1D scan on the Pallas backend is EXACTLY one pallas_call at
         every lane count -- the striped (c, c*bpl) grid with its in-kernel
         carry rebuild never falls back to one launch per lane or to a
         host combine pass;
      b. bf16 ingestion is staging-free: NO n-sized convert_element_type /
         pad / concatenate outside the pallas_call, including the
         reverse-direction relayout and the exclusive prefix (whose exact
         shift is sliced inside the kernel's own output, not re-padded);
      c. measured launch-boundary bytes == ``cost_model.scan_hbm_bytes``'s
         ``launch_io`` at cores in {1, 2, 4} for both native dtypes: one
         native read of the caller's buffer plus the block-padded prefix
         write, with the carry-rebuild refetch charged OUTSIDE the launch
         boundary (it re-streams blocks through the same BlockSpec, so a
         drift here means the kernel grew a real extra operand).
    """
    import jax

    from repro import reduce as R
    from repro.core import cost_model
    from repro.reduce import inspect as rinspect

    n = 300_000
    xb = jnp.zeros((n,), jnp.bfloat16)
    xf = jnp.zeros((n,), jnp.float32)
    for c in (1, 2, 4):
        for x in (xb, xf):
            plan = R.scan_plan_for(
                x.shape, x.dtype, backend="pallas_fused", num_cores=c
            )
            fn = lambda v, p=plan: R.scan(v, plan=p)
            nc = rinspect.count_pallas_calls(fn, x)
            assert nc == 1, f"scan[{x.dtype}, c={c}]: {nc} pallas_calls"  # (a)
            model = cost_model.scan_hbm_bytes(
                n, x.dtype.itemsize, m=plan.m, num_cores=c,
                tiles_per_block=plan.tiles_per_block,
            )
            measured = rinspect.pallas_io_bytes(jax.make_jaxpr(fn)(x))
            assert measured == model.launch_io, (
                f"scan[{x.dtype}, c={c}]: lowered pallas_call moves "
                f"{measured} bytes but scan_hbm_bytes models "
                f"{model.launch_io} -- kernel operands and the traffic "
                "model have diverged"
            )  # (c)
    # (b) bf16 staging-free, incl. the direction/inclusivity variants
    for kw in ({}, {"reverse": True}, {"inclusive": False}):
        fn = lambda v, k=kw: R.scan(v, backend="pallas_fused", **k)
        rinspect.assert_staging_free(fn, xb)
        nc = rinspect.count_pallas_calls(fn, xb)
        assert nc == 1, f"scan[bf16, {kw}]: {nc} pallas_calls"


def check_distributed_reduce() -> None:
    """The mesh_axes= reduce path, gated on the lowered shard_map program
    (run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` in
    the multidevice CI job; degrades gracefully to fewer devices):

      a. the guarded clipping statistic with census inside a shard_map body
         is still EXACTLY one pallas_call -- one launch PER DEVICE, the
         local shard's whole additive row (per-leaf sums, raw total,
         census) from a single kernel;
      b. modeled interconnect bytes == the lowered program's collective
         receive bytes: ``cost_model.interconnect_bytes(slots, world)``
         against ``inspect.collective_recv_bytes`` -- the same
         model==lowered discipline as the HBM gate;
      c. the only collectives in the lowering are ``all_gather`` -- no
         opaque ``psum`` whose wire-reduction order could break the
         bitwise-replica-identical contract.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    from repro import reduce as R
    from repro.core import collectives as coll
    from repro.core import cost_model
    from repro.reduce import inspect as rinspect

    world = len(jax.devices())
    mesh = jax.make_mesh((world,), ("data",))
    tree = {
        "w": jnp.ones((world * 40, 64), jnp.bfloat16),
        "b": jnp.ones((world * 300,), jnp.bfloat16),
    }
    nleaves = len(jax.tree.leaves(tree))

    def stat(t):
        return R.reduce_tree(
            t, "norm2", backend="pallas_fused", census=True,
            mesh_axes=("data",),
        )

    fn = coll.shard_map_unchecked(
        stat, mesh=mesh, in_specs=(P("data"),), out_specs=P()
    )
    n = rinspect.count_pallas_calls(fn, tree)
    assert n == 1, f"distributed census stat: {n} pallas_calls/device"  # (a)
    jaxpr = jax.make_jaxpr(fn)(tree)
    names = {name for name, _, _ in rinspect.collective_eqns(jaxpr)}
    assert names <= {"all_gather"}, (
        f"opaque collectives in the deterministic combine lowering: "
        f"{names - {'all_gather'}}"
    )  # (c)
    # row = per-leaf sums + raw total + census counts (per-leaf + total)
    slots = nleaves + 1 + (nleaves + 1)
    want = cost_model.interconnect_bytes(slots, world)
    measured = rinspect.collective_recv_bytes(jaxpr)
    assert measured == want.recv_per_device, (
        f"distributed combine receives {measured} B/device but "
        f"interconnect_bytes({slots}, {world}) models "
        f"{want.recv_per_device} -- row layout and the ICI model diverged"
    )  # (b)
    print(
        f"check_bench --distributed: OK ({world} devices, 1 launch/device, "
        f"{measured} B/device over all_gather == model)"
    )


def main(argv=None) -> None:
    args = list(sys.argv[1:] if argv is None else argv)
    if "--distributed" in args:
        # standalone multidevice gate: no BENCH json required (the bench
        # artifact is the single-device job's business)
        check_distributed_reduce()
        return
    if "--serve" in args:
        # standalone serving gate (the serve CI job): no BENCH json required
        check_serve_guard()
        return
    path = args[0] if args else "BENCH_reduce.json"
    check_report(path)
    check_launch_counts()
    check_staging_free()
    check_optimizer_step()
    check_guarded_step()
    check_serve_guard()
    check_scan()
    print(
        f"check_bench: {path} OK (structure, MMA totals, HBM traffic, "
        "launch counts, staging-free ingestion, one-trip optimizer step, "
        "guarded step census, serve guard, mma scan)"
    )


if __name__ == "__main__":
    main()
