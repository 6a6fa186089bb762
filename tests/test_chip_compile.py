"""Compile the engine's kernels and the full-width train step for a
described TPU v5e, with no chip attached.

Interpret mode cannot see what the chip's compiler refuses: tiling,
layouts, scalar stores into VMEM, fast-memory limits. Every case here
lowers at real widths for one device of a described ``v5e:2x2`` topology
and asserts that the compiled program holds a ``tpu_custom_call`` -- the
kernel was compiled for the chip, not interpreted.

The topology is described inside a module-scoped fixture and never while a
module is imported: only the worker that runs this file loads the TPU
compiler library. The persistent compile cache is off around these
compiles (an entry written here cannot be read back without a chip).
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import reduce as R
from repro.configs import TrainConfig, get_arch
from repro.core import cost_model
from repro.kernels import common
from repro.kernels.cross_entropy import kernel as ce_kernel
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.mma_reduce import kernel as K
from repro.kernels.mma_reduce import ops
from repro.kernels.row_moments import ops as rm_ops
from repro.kernels.scan import mma_scan_pallas
from repro.launch.steps import make_jitted_guarded_train_step
from repro.models import init_params
from repro import optim

GiB = 2**30
V5E_HBM = cost_model.peaks_for("TPU v5 lite").hbm_bytes
# olmo-1b widths: d_model 2048, d_ff 8192, vocab 50304, 16 heads of 128
D, FF, VOCAB, HEADS, HEAD_DIM = 2048, 8192, 50304, 16, 128
N = D * FF  # one ffn weight, flattened


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Engine entry points resolve ``interpret=None`` from the default
    backend, which is the CPU here: steer them to compiled mode."""
    monkeypatch.setattr(
        common, "resolve_interpret",
        lambda interpret: False if interpret is None else interpret,
    )


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


BF16, F32 = jnp.bfloat16, jnp.float32
CLIP_FORK = ((), ("clip_coeff", 1.0))

# name -> (fn, [(shape, dtype), ...]); every kernel body at real widths
KERNELS = {
    "fused_bf16": (
        lambda x: K.reduce_fused(x, interpret=False), [((N,), BF16)]),
    "fused_bf16_census": (
        lambda x: K.reduce_fused(x, census=True, interpret=False),
        [((N,), BF16)]),
    "fused_bf16_epilogue": (
        lambda x: K.reduce_fused(x, prologue="square", epilogue=(("sqrt",),),
                                 census=True, interpret=False),
        [((N,), BF16)]),
    "fused_f32": (
        lambda x: K.reduce_fused(x, interpret=False), [((N,), F32)]),
    "fused_f32_census": (
        lambda x: K.reduce_fused(x, census=True, interpret=False),
        [((N,), F32)]),
    "fused_f32_epilogue": (
        lambda x: K.reduce_fused(x, prologue="square", epilogue=(("sqrt",),),
                                 interpret=False),
        [((N,), F32)]),
    "fused_kahan": (
        lambda x: K.reduce_fused(x, kahan=True, compute_dtype=F32,
                                 interpret=False),
        [((N,), F32)]),
    "fused_moments": (
        lambda x: K.reduce_fused(x, prologue="moments", interpret=False),
        [((N,), BF16)]),
    "tiles": (
        lambda x: K.reduce_tiles(x, interpret=False), [((N,), BF16)]),
    "tiles_moments": (
        lambda x: K.reduce_tiles(x, prologue="moments", interpret=False),
        [((N,), BF16)]),
    "segments_census": (
        lambda x: ops.mma_sum_segments_pallas(
            x, (0, 1000, 70_000, N), census=True, interpret=False),
        [((N,), BF16)]),
    "segments_moments": (
        lambda x: ops.mma_sum_segments_pallas(
            x, (0, 1000, 70_000, N), prologue="moments", interpret=False),
        [((N,), F32)]),
    "parts_128_census_fork": (
        lambda *xs: ops.mma_sum_parts_pallas(
            list(xs), prologue="square", total_chains=CLIP_FORK,
            census=True, interpret=False),
        [((FF, D), BF16)] + [((D,), BF16)] * (ops.PARTS_KERNEL_MAX - 1)),
    "scan_f32": (
        lambda x: mma_scan_pallas(x, interpret=False), [((N,), F32)]),
    "scan_bf16": (
        lambda x: mma_scan_pallas(x, interpret=False), [((N,), BF16)]),
    # below SMALL_FLAT elements a flat operand enters as a (1, n) row
    "small_fused": (
        lambda x: K.reduce_fused(x, census=True, interpret=False),
        [((5,), BF16)]),
    "small_tiles": (
        lambda x: K.reduce_tiles(x, interpret=False), [((300,), F32)]),
    "small_segments": (
        lambda x: ops.mma_sum_segments_pallas(x, (0, 100, 512),
                                              interpret=False),
        [((512,), BF16)]),
    "small_parts": (
        lambda *xs: ops.mma_sum_parts_pallas(list(xs), census=True,
                                             interpret=False),
        [((33,), BF16), ((70_000,), BF16), ((512,), F32)]),
    "small_scan": (
        lambda x: mma_scan_pallas(x, interpret=False), [((128,), BF16)]),
    "flash_attention": (
        lambda q, k, v: fa_ops.flash_attention(q, k, v, interpret=False),
        [((1, HEADS, 2048, HEAD_DIM), BF16)] * 3),
    "cross_entropy": (
        lambda lg, y: ce_kernel.cross_entropy_call(lg, y, interpret=False),
        [((4096, VOCAB), BF16), ((4096,), jnp.int32)]),
    "rmsnorm": (
        lambda x, g: rm_ops.rmsnorm(x, g, 1e-6, False),
        [((4096, D), BF16), ((D,), BF16)]),
    "layernorm_np": (
        lambda x: rm_ops.layernorm_np(x, 1e-5, False), [((4096, D), BF16)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    specs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    _assert_kernel(_compile(fn, *specs))


# name -> (fn, [(shape, dtype), ...]); the engine's public entry points on
# the kernel backend, as the optimizer, the serving census and the planner
# call them
ENGINE = {
    "reduce_sum_bf16": (
        lambda x: R.reduce(x, backend="pallas_fused"), [((FF, D), BF16)]),
    "reduce_norm2_bf16": (
        lambda x: R.reduce(x, kind="norm2", backend="pallas_fused"),
        [((FF, D), BF16)]),
    "reduce_norm2_f32": (
        lambda x: R.reduce(x, kind="norm2", backend="pallas_fused"),
        [((FF, D), F32)]),
    "reduce_hier": (
        lambda x: R.reduce(x, backend="pallas_hier"), [((FF, D), BF16)]),
    "reduce_kahan": (
        lambda x: R.reduce(x, backend="pallas_fused", precision="kahan",
                           compute_dtype="float32"),
        [((FF, D), F32)]),
    "reduce_tree_census": (
        lambda *t: R.reduce_tree(list(t), "norm2", backend="pallas_fused",
                                 census=True),
        [((VOCAB, D), BF16), ((16, D, FF), BF16), ((D,), BF16)]),
    "reduce_tree_clip_fork": (
        lambda *t: R.reduce_tree(list(t), "norm2", backend="pallas_fused",
                                 epilogue=list(CLIP_FORK)),
        [((VOCAB, D), BF16), ((16, D, FF), BF16), ((D,), BF16)]),
    "reduce_many": (
        lambda *a: R.reduce_many(list(a), backend="pallas_fused"),
        [((VOCAB, D), BF16), ((FF, D), F32), ((D,), BF16)]),
    "scan": (
        lambda x: R.scan(x, backend="pallas_fused"), [((N,), F32)]),
    "serving_census": (
        lambda lg: R.reduce_tree([lg[i] for i in range(4)], "sumsq",
                                 backend="pallas_fused", census=True,
                                 return_per_leaf=True),
        [((4, 1, VOCAB), BF16)]),
}


@pytest.mark.parametrize("name", sorted(ENGINE))
def test_engine_entry_compiles_for_v5e(name, one_chip, compiled_kernels):
    fn, shapes = ENGINE[name]
    specs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    _assert_kernel(_compile(fn, *specs))


def test_full_width_olmo_train_step_fits_one_v5e(one_chip, compiled_kernels):
    """The guarded olmo-1b step on pallas_fused at batch 1 x seq 2048:
    compiles for one v5e, keeps its clipping statistic in a kernel, and
    needs less than the chip's 16 GiB."""
    cfg = get_arch("olmo-1b")
    tcfg = TrainConfig()

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda k: init_params(k, cfg)[0], jax.random.PRNGKey(0)))
    opt = on_chip(jax.eval_shape(optim.init_state, params))
    guard = on_chip(jax.eval_shape(optim.init_guard_state))
    feed = {"tokens": jax.ShapeDtypeStruct((1, 2049), jnp.int32,
                                           sharding=one_chip)}
    R.set_default_backend("pallas_fused")  # as train --reduce-backend does
    try:
        step = make_jitted_guarded_train_step(cfg, tcfg)
        compiled = step.lower(params, opt, guard, feed).compile()
    finally:
        R.set_default_backend(None)
    _assert_kernel(compiled)
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert need < V5E_HBM, need / GiB


def test_guarded_decode_step_writes_cache_in_place_on_v5e(one_chip,
                                                          compiled_kernels):
    """The guarded decode step at internlm2-1.8b's widths (two of its
    layers) with the chat cell's 48 slots of 1,025 positions: every cache
    leaf is aliased to an output, and the step's temporaries hold neither a
    copy of the cache nor one layer's slice of it."""
    from repro.launch.serve import GuardedEngine
    from repro.models import make_caches
    from repro.models.model import split_caches

    cfg = dataclasses.replace(get_arch("internlm2-1.8b"), n_layers=2)
    slots, s_max = 48, 1025
    eng = object.__new__(GuardedEngine)  # its shapes only: no weights made
    eng.cfg, eng.s_max, eng.slots, eng.ctx = cfg, s_max, slots, None
    eng._guarded_decode = {}

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda k: init_params(k, cfg)[0], jax.random.PRNGKey(0)))
    indexed, rest = split_caches(on_chip(jax.eval_shape(
        lambda: make_caches(cfg, slots, s_max))))
    lowered = eng._decode_fn("pallas_fused").lower(
        params, indexed, rest,
        jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((slots,), jnp.float32, sharding=one_chip))
    leaves = jax.tree.leaves(indexed)
    assert lowered.as_text().count("tf.aliasing_output") == len(leaves)
    compiled = lowered.compile()
    _assert_kernel(compiled)
    mem = compiled.memory_analysis()
    # the chip's tiles pad the small ``slot_pos`` leaf
    assert mem.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize
                                          for a in leaves)
    layer_kv = max(a.size * a.dtype.itemsize for a in leaves) // cfg.n_layers
    assert mem.temp_size_in_bytes < layer_kv // 8, mem.temp_size_in_bytes


def test_deepseek_v2_lite_guarded_decode_writes_cache_in_place_on_v5e(
        one_chip, compiled_kernels):
    """The guarded decode step at deepseek-v2-lite's widths (its dense
    first layer and two MoE layers, 8 experts held) with the chat cell's
    128 slots of 1,025 positions: every MLA cache leaf, the leading
    layer's included, is aliased to an output, no instruction copies the
    latent cache or a layer's slice of it, and the temporaries stay under
    one layer's bfloat16 latent (the normalised latent the dots read; a
    cache of 576-wide entries laid out batch-minor copies each layer's
    slice and needs more than twice that)."""
    import re

    from repro.launch.serve import GuardedEngine
    from repro.models import make_caches
    from repro.models.model import split_caches

    full = get_arch("deepseek-v2-lite")
    cfg = dataclasses.replace(full, n_layers=3, moe=dataclasses.replace(
        full.moe, held_count=8))
    slots, s_max = 128, 1025
    eng = object.__new__(GuardedEngine)  # its shapes only: no weights made
    eng.cfg, eng.s_max, eng.slots, eng.ctx = cfg, s_max, slots, None
    eng._guarded_decode = {}

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda k: init_params(k, cfg)[0], jax.random.PRNGKey(0)))
    indexed, rest = split_caches(on_chip(jax.eval_shape(
        lambda: make_caches(cfg, slots, s_max))))
    assert set(indexed) == {"lead", "units"}
    lowered = eng._decode_fn("pallas_fused").lower(
        params, indexed, rest,
        jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((slots,), jnp.float32, sharding=one_chip))
    leaves = jax.tree.leaves(indexed)
    assert len(leaves) == 6
    assert lowered.as_text().count("tf.aliasing_output") == len(leaves)
    compiled = lowered.compile()
    _assert_kernel(compiled)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize
                                          for a in leaves)
    stacked = indexed["units"]["pos0"]["ckv"]
    layer_shape = ",".join(str(d) for d in stacked.shape[1:])
    copies = re.findall(r"= (\w+\[[0-9,]*\])[^ ]* copy\(", compiled.as_text())
    assert not [c for c in copies if c.endswith(f"{layer_shape}]")], copies
    layer = stacked.size // stacked.shape[0] * 2        # one layer, bf16
    assert mem.temp_size_in_bytes < layer, mem.temp_size_in_bytes
