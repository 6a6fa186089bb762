"""Multi-device collective tests. jax locks the host device count at first
init, so these run in a subprocess with XLA_FLAGS=8 fake devices -- keeping
the main pytest process single-device per the dry-run isolation rule."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(body: str):
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.core import collectives as C
        """
    ) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=REPO_SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=420)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


def test_hierarchical_psum_and_mma_local():
    run_sub("""
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    x = jnp.arange(32.0).reshape(8, 4)

    def body(xs):
        return C.local_mma_then_psum(xs, ("model", "data"))

    out = jax.jit(C.shard_map(body, mesh=mesh,
                                in_specs=P("data", "model"),
                                out_specs=P()))(x)
    np.testing.assert_allclose(float(out), float(x.sum()), rtol=1e-5)
    print("hierarchical ok")
    """)


def test_ring_all_reduce_matches_psum():
    run_sub("""
    mesh = jax.make_mesh((8,), ("data",))
    x = jnp.arange(8 * 13, dtype=jnp.float32).reshape(8, 13)

    def body(xs):
        ring = C.ring_all_reduce(xs, "data")
        ref = jax.lax.psum(xs, "data")
        return ring, ref

    ring, ref = jax.jit(C.shard_map(body, mesh=mesh,
                                      in_specs=P("data", None),
                                      out_specs=(P("data", None), P("data", None))))(x)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(ref), rtol=1e-6)
    print("ring ok")
    """)


def test_compressed_psum_error_feedback():
    run_sub("""
    mesh = jax.make_mesh((8,), ("pod",))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

    def body(xs, err):
        out, new_err = C.compressed_psum(xs, "pod", err)
        ref = jax.lax.psum(xs, "pod")
        return out, new_err, ref

    f = jax.jit(C.shard_map(body, mesh=mesh,
                              in_specs=(P("pod", None), P("pod", None)),
                              out_specs=(P("pod", None),) * 3))
    err = jnp.zeros_like(x)
    out, err, ref = f(x, err)
    rel = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    assert rel < 0.05, rel          # int8 quantization error bounded
    # error feedback: the residual carried forward equals what was lost
    # so repeated reduction of a CONSTANT gradient converges in mean
    acc = jnp.zeros_like(out)
    e = jnp.zeros_like(x)
    for i in range(20):
        o, e, _ = f(x, e)
        acc = acc + o
    drift = float(jnp.max(jnp.abs(acc / 20 - ref)))
    assert drift < float(jnp.max(jnp.abs(ref))) * 0.01, drift
    print("compressed ok")
    """)


def test_sharded_train_step_runs_on_mesh():
    """End-to-end: FSDP+TP sharded train step on a (2,4) mesh, real numerics
    (tiny olmo), asserting the loss is finite and params update."""
    run_sub("""
    import dataclasses
    from repro.configs import TINY_ARCHS, TrainConfig
    from repro.launch import sharding as SH
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import make_train_step
    from repro.models import init_params, context as CTX
    from repro import optim

    mesh = make_mesh((2, 4), ("data", "model"))
    CTX.set_activation_sharding(NamedSharding(mesh, P("data", None, None)))
    cfg = TINY_ARCHS["internlm2-1.8b"]
    params, axes = init_params(jax.random.PRNGKey(0), cfg)
    pshard = SH.param_shardings(axes, mesh, SH.DEFAULT_RULES, params)
    params = jax.tree.map(jax.device_put, params, pshard)
    opt = optim.init_state(params)
    step = jax.jit(make_train_step(cfg, TrainConfig(microbatches=2), mesh,
                                   param_shardings=pshard))
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)
    toks = jax.device_put(toks, NamedSharding(mesh, P("data", None)))
    p1, o1, m = step(params, opt, {"tokens": toks})
    assert np.isfinite(float(m["loss"]))
    delta = sum(float(jnp.max(jnp.abs(a - b))) for a, b in
                zip(jax.tree.leaves(p1), jax.tree.leaves(params)))
    assert delta > 0
    print("sharded step ok, loss", float(m["loss"]))
    """)
