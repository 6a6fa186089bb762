"""Sharding-rule unit tests (pure logic; no devices needed) + the HLO
collective/depth parsers on synthetic module text."""

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch import sharding as SH


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((1, 1), ("data", "model"))


class FakeMesh:
    """Shape-only stand-in so divisibility logic is testable without 256
    devices."""
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def spec(axes, shape=None, rules=SH.DEFAULT_RULES, mesh_shape=None):
    m = FakeMesh(mesh_shape or {"data": 16, "model": 16})
    return SH.spec_for(axes, rules, m, shape)


def test_basic_mapping():
    assert spec(("embed", "ffn")) == P("data", "model")
    assert spec(("vocab", None)) == P("model")
    assert spec(None) == P()


def test_divisibility_guard():
    # vocab 50280 % 16 != 0 -> partition dropped
    assert spec(("vocab", "embed"), shape=(50280, 1536)) == P(None, "data")
    assert spec(("vocab", "embed"), shape=(50432, 1536)) == P("model", "data")


def test_axis_used_once():
    # both dims want "model": second falls back to None
    assert spec(("ffn", "heads")) == P("model")


def test_multi_axis_fsdp():
    m3 = {"pod": 2, "data": 16, "model": 16}
    s = spec(("embed", "ffn"), shape=(6144, 10752),
             rules=SH.BIG_MODEL_RULES, mesh_shape=m3)
    assert s == P(("pod", "data"), "model")
    # on a single-pod mesh the pod axis is skipped
    s1 = spec(("embed", "ffn"), shape=(6144, 10752), rules=SH.BIG_MODEL_RULES)
    assert s1 == P("data", "model")


def test_small_model_rules_drop_tp():
    assert spec(("embed", "ffn"), rules=SH.SMALL_MODEL_RULES) == P("data")
    assert spec(("embed", "heads"), rules=SH.SMALL_MODEL_RULES) == P("data")
    # experts keep EP
    assert spec(("experts", "embed", "ffn"), rules=SH.SMALL_MODEL_RULES) == \
        P("model", "data")


def test_batch_partition_guard(mesh):
    big = jax.make_mesh((1, 1), ("data", "model"))
    assert SH.batch_partition(big, 8) == "data"
    assert SH.batch_partition(big, 7) == "data"  # 7 % 1 == 0
    fake = FakeMesh({"data": 16, "model": 16})
    assert SH.batch_partition(fake, 1) is None    # long_500k: replicated
    assert SH.batch_partition(fake, 256) == "data"


# --------------------------- HLO parsers -------------------------------------

SYNTH_HLO = """
%region_inner (arg: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {
  %ar = f32[8,128]{1,0} all-reduce(%x), replica_groups={}, to_apply=%add
  ROOT %t = (s32[], f32[8,128]) tuple(%i, %ar)
}

%region_outer (arg: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {
  %ag = f32[16,128]{1,0} all-gather(%y), dimensions={0}
  %w = (s32[], f32[8,128]) while(%arg), condition=%c, body=%region_inner
  ROOT %t2 = (s32[], f32[8,128]) tuple(%i2, %x2)
}

ENTRY %main (p0: f32[8,128]) -> f32[8,128] {
  %g = f32[256]{0} all-reduce(%p), to_apply=%add
  %w0 = (s32[], f32[8,128]) while(%init), condition=%c0, body=%region_outer
  ROOT %r = f32[8,128] get-tuple-element(%w0), index=1
}
"""


def test_collective_entry_vs_loop_buckets():
    from repro.launch.dryrun import parse_collective_bytes

    out = parse_collective_bytes(SYNTH_HLO)
    assert out["entry"]["all-reduce"]["count"] == 1
    assert out["entry"]["all-reduce"]["bytes"] == 256 * 4
    assert out["loop"]["all-reduce"]["count"] == 1
    assert out["loop"]["all-gather"]["count"] == 1
    # wire factors: AR x2, AG x1
    assert out["entry_wire_bytes"] == 2 * 256 * 4


def test_collective_depth_attribution():
    from repro.launch.dryrun import parse_collective_depths

    d = parse_collective_depths(SYNTH_HLO)
    assert d["0"] == 2 * 256 * 4                 # entry AR, wire x2
    assert d["1"] == 16 * 128 * 4                # AG in the depth-1 body
    assert d["2"] == 2 * 8 * 128 * 4             # AR in the depth-2 body


def test_deepseek_v2_lite_param_and_cache_specs(mesh):
    """DeepSeek-V2-Lite's new leaves take the existing logical axes: the
    direct query projection shards heads, routed experts go expert
    parallel, the shared experts shard like a dense feed-forward; the
    leading dense layer's latent cache has no layer axis, the stacked
    layers' has an unsharded one."""
    from repro.configs import ARCHS, TINY_ARCHS
    from repro.models import init_params, make_caches

    cfg = ARCHS["deepseek-v2-lite"]
    shapes = jax.eval_shape(lambda k: init_params(k, cfg)[0],
                            jax.random.PRNGKey(0))
    _, axes = init_params(jax.random.PRNGKey(0), TINY_ARCHS["deepseek-v2-lite"])
    ffn = axes["units"]["pos0"]["ffn"]
    q = axes["lead"]["pos0"]["mix"]["q"]["w"]
    assert spec(q) == P("data", "model")
    assert spec(ffn["gate"][1:]) == P("model", "data")
    assert spec(ffn["shared"]["gate"]["w"][1:]) == P("data", "model")
    assert "router" in ffn and "shared" in ffn
    assert "q_down" not in axes["lead"]["pos0"]["mix"]
    e = shapes["units"]["pos0"]["ffn"]["gate"]
    assert e.shape == (26, 64, 2048, 1408)
    caches = jax.eval_shape(
        lambda: make_caches(TINY_ARCHS["deepseek-v2-lite"], 2, 16))
    specs = SH.cache_shardings(caches, TINY_ARCHS["deepseek-v2-lite"], mesh)
    for leaf in ("ckv", "k_rope"):
        assert len(specs["lead"]["pos0"][leaf].spec) <= 3
        assert specs["units"]["pos0"][leaf].spec[0] is None
