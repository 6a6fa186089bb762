"""Multi-head Latent Attention (DeepSeek-V2 / MiniCPM3-4B).

Q and KV are projected through low-rank latents; the KV cache stores only the
compressed latent ``c_kv`` (+ the shared RoPE key), which is MLA's memory
contribution. Decode attends in the latent space (weight absorption,
``mla_decode``).

Without a q-LoRA rank (DeepSeek-V2-Lite) the query is one direct
projection ``q``. With ``cfg.rope_scaling`` the rope dims rotate at YaRN's
inverse frequencies (``rope_inv_freq``) and the softmax scale carries
YaRN's attention factor (``softmax_scale``); with ``rope_interleave`` the
rope dims are taken as interleaved pairs and permuted to rotate-half order
first, as DeepSeek-V2's ``apply_rotary_pos_emb`` does. Every op here runs
in the ``mla`` scope.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as A
from repro.models import layers as L
from repro.models import params as P


def mla_init(key, cfg):
    m = cfg.mla
    d = cfg.d_model
    h = cfg.n_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    ks = P.split(key, 7)
    pkv_d, akv_d = P.dense_init(
        ks[2], d, m.kv_lora_rank + m.qk_rope_dim, ("embed", None), cfg_dtype(cfg)
    )
    pkv_u, akv_u = P.dense_init(
        ks[3], m.kv_lora_rank, h * (m.qk_nope_dim + m.v_head_dim), (None, "heads"), cfg_dtype(cfg)
    )
    po, ao = P.dense_init(ks[4], h * m.v_head_dim, d, ("heads", "embed"), cfg_dtype(cfg))
    kvn, akvn = P.norm_init("rmsnorm", m.kv_lora_rank, cfg_dtype(cfg))
    params = {"kv_down": pkv_d, "kv_up": pkv_u, "o": po, "kv_norm": kvn}
    axes = {"kv_down": akv_d, "kv_up": akv_u, "o": ao, "kv_norm": akvn}
    if m.q_lora_rank is None:
        params["q"], axes["q"] = P.dense_init(
            ks[0], d, h * qk, ("embed", "heads"), cfg_dtype(cfg))
    else:
        params["q_down"], axes["q_down"] = P.dense_init(
            ks[0], d, m.q_lora_rank, ("embed", None), cfg_dtype(cfg))
        params["q_up"], axes["q_up"] = P.dense_init(
            ks[1], m.q_lora_rank, h * qk, (None, "heads"), cfg_dtype(cfg))
        params["q_norm"], axes["q_norm"] = P.norm_init(
            "rmsnorm", m.q_lora_rank, cfg_dtype(cfg))
    return params, axes


def cfg_dtype(cfg):
    return jnp.dtype(cfg.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor: 0.1 * mscale * ln(factor) + 1 (1 when the
    factor does not stretch)."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(cfg):
    """(inverse frequencies of the rope dims (qk_rope_dim / 2,), the cos/sin
    scale). Without rope scaling: theta ** (-2i / dim) and 1. With YaRN:
    the interpolated frequencies (/ factor) where a dim turns fewer than
    ``beta_slow`` times over the original context, the original ones where
    it turns more than ``beta_fast`` times, and a linear ramp between
    (DeepSeek-V2's ``yarn_find_correction_range`` and
    ``yarn_linear_ramp_mask``)."""
    dim, base = cfg.mla.qk_rope_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    y = cfg.rope_scaling
    if y is None:
        return extra.astype(np.float32), 1.0

    def corr_dim(rotations):
        return (dim * math.log(y.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr_dim(y.beta_fast)), 0)
    high = min(math.ceil(corr_dim(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                # 1: the original frequency
    inv = extra / y.factor * (1.0 - keep) + extra * keep
    scale = (yarn_mscale(y.factor, y.mscale)
             / yarn_mscale(y.factor, y.mscale_all_dim))
    return inv.astype(np.float32), scale


def softmax_scale(cfg) -> float:
    """q_head_dim ** -0.5, times YaRN's mscale(mscale_all_dim) squared."""
    m, y = cfg.mla, cfg.rope_scaling
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _rope(x, positions, cfg):
    """Rope over the last dim of x (..., S, H, qk_rope_dim) at
    ``positions`` (..., S)."""
    m = cfg.mla
    if cfg.rope_scaling is None and not m.rope_interleave:
        return L.rope(x, positions, cfg.rope_theta)
    if m.rope_interleave:
        # pairs (x0, x1), (x2, x3), ... -> (x0, x2, ..., x1, x3, ...)
        x = x.reshape(x.shape[:-1] + (-1, 2)).swapaxes(-1, -2).reshape(x.shape)
    inv, mscale = rope_inv_freq(cfg)
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(inv)
    cos = (jnp.cos(ang) * mscale)[..., None, :]
    sin = (jnp.sin(ang) * mscale)[..., None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _query(p, x, cfg):
    """The per-head query (B, S, H, qk_nope + qk_rope), rope not applied."""
    m = cfg.mla
    b, s, _ = x.shape
    if m.q_lora_rank is None:
        q = P.dense_apply(p["q"], x)
    else:
        cq = P.dense_apply(p["q_down"], x)
        cq = L.norm_apply("rmsnorm", p["q_norm"], cq, eps=cfg.norm_eps,
                          mma=cfg.mma_reductions)
        q = P.dense_apply(p["q_up"], cq)
    return q.reshape(b, s, cfg.n_heads, m.qk_nope_dim + m.qk_rope_dim)


def _expand(p, x, positions, cfg):
    """Project x to per-head q, k, v (rope applied). Returns (q, k, v)."""
    m = cfg.mla
    h = cfg.n_heads
    b, s, _ = x.shape
    ckv_full = P.dense_apply(p["kv_down"], x)
    ckv_raw, k_rope = ckv_full[..., : m.kv_lora_rank], ckv_full[..., m.kv_lora_rank:]
    if m.q_lora_rank is None:
        q = _query(p, x, cfg)
        ckv = L.norm_apply("rmsnorm", p["kv_norm"], ckv_raw, eps=cfg.norm_eps,
                           mma=cfg.mma_reductions)
    else:
        # Both latent norms are independent functions of x, so their
        # statistics batch into one segmented reduction pass (reduce_many;
        # see layers.rmsnorm_apply_many) -- one launch per layer, not two.
        cq = P.dense_apply(p["q_down"], x)
        cq, ckv = L.rmsnorm_apply_many(
            (p["q_norm"], p["kv_norm"]),
            (cq, ckv_raw),
            eps=cfg.norm_eps,
            mma=cfg.mma_reductions,
        )
        q = P.dense_apply(p["q_up"], cq).reshape(b, s, h, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = _rope(q_rope, positions, cfg)

    k_rope = _rope(k_rope[:, :, None, :], positions, cfg)  # shared head
    kv = P.dense_apply(p["kv_up"], ckv).reshape(b, s, h, m.qk_nope_dim + m.v_head_dim)
    k_nope, v = kv[..., : m.qk_nope_dim], kv[..., m.qk_nope_dim:]
    k_rope_b = jnp.broadcast_to(k_rope, (b, s, h, m.qk_rope_dim))
    q_full = jnp.concatenate([q_nope, q_rope], -1)
    k_full = jnp.concatenate([k_nope, k_rope_b], -1)
    return q_full, k_full, v, ckv_full


@jax.named_scope("mla")
def mla_train(p, x, positions, cfg):
    q, k, v, _ = _expand(p, x, positions, cfg)
    out = A.flash_attention_xla(
        q, k, v, causal=True, mma=cfg.mma_reductions,
        sm_scale=softmax_scale(cfg),
    )
    b, s, _, _ = out.shape
    return P.dense_apply(p["o"], out.reshape(b, s, -1))


def make_mla_cache(batch: int, s_max: int, cfg):
    """The latent ``ckv`` (B, S, kv_lora_rank) and the shared rope key
    ``k_rope`` (B, S, qk_rope_dim) as two leaves. One (B, S, R + dr) leaf
    at DeepSeek-V2's 512 + 64 is no multiple of 128 lanes wide: the TPU
    compiler then lays the stacked cache out batch-minor and copies every
    layer's slice to read it; split, only the rope key's slice (a ninth of
    the bytes) is still copied."""
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, s_max, m.kv_lora_rank), cfg_dtype(cfg)),
        "k_rope": jnp.zeros((batch, s_max, m.qk_rope_dim), cfg_dtype(cfg)),
        "slot_pos": jnp.full((s_max,), -1, jnp.int32),
    }


@jax.named_scope("mla")
def mla_fill_cache(p, x, positions, cache, cfg):
    """Prefill the compressed-latent cache. RoPE on the shared key is applied
    at *write* time (positions are absolute)."""
    m = cfg.mla
    ckv_full = P.dense_apply(p["kv_down"], x)
    k_rope = _rope(
        ckv_full[..., m.kv_lora_rank:][:, :, None, :], positions, cfg
    )[:, :, 0, :]
    s = x.shape[1]
    ckv = jax.lax.dynamic_update_slice(
        cache["ckv"], ckv_full[..., : m.kv_lora_rank], (0, 0, 0))
    kr = jax.lax.dynamic_update_slice(cache["k_rope"], k_rope, (0, 0, 0))
    slot_pos = cache["slot_pos"].at[:s].set(jnp.arange(s))
    return {"ckv": ckv, "k_rope": kr, "slot_pos": slot_pos}


@jax.named_scope("mla")
def mla_decode(p, x_t, cache, pos, cfg):
    """One decode step from the compressed cache, *weight-absorbed*.

    Production MLA serving never expands per-head K/V over the cache (that
    materializes a (B, S, H, d) tensor per layer per step -- caught by the
    dry-run at 29 GB/device temp on decode_32k). Instead the up-projections
    are folded into the query and output:

      score_h(i) = (W_uk_h^T q_nope_h) . c_i + q_rope_h . k_rope_i
      out_h      = W_uv_h^T (sum_i p_h(i) c_i)

    so attention runs entirely in the R-dim latent space; per-step memory is
    O(B*S*R) reads + O(B*H*R) temporaries. The cache is only read; returns
    (out, the token's new entries {"ckv": (B, 1, R), "k_rope": (B, 1, dr)}),
    which the caller writes at position ``pos``.
    """
    m = cfg.mla
    h = cfg.n_heads
    b = x_t.shape[0]
    posb = jnp.broadcast_to(pos, (b, 1))
    # query
    q = _query(p, x_t, cfg)
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = _rope(q_rope, posb, cfg)[:, 0]                    # (B, H, dr)
    # this step's latent: attended beside the cache, written by the caller
    ckv_full = P.dense_apply(p["kv_down"], x_t)
    k_rope_t = _rope(
        ckv_full[..., m.kv_lora_rank:][:, :, None, :], posb, cfg
    )[:, :, 0, :]
    stored = {"ckv": ckv_full[..., : m.kv_lora_rank], "k_rope": k_rope_t}
    # normalized latents + shared rope key: the cache's, read where they
    # lie, and the new token's
    def latents(c):
        return L.norm_apply(
            "rmsnorm", p["kv_norm"], c["ckv"],
            eps=cfg.norm_eps, mma=cfg.mma_reductions,
        ), c["k_rope"]

    c_all, k_rope_all = latents(cache)                          # (B, S, R)
    c_new, k_rope_new = latents(stored)                         # (B, 1, R)
    # absorb W_uk into the query: q_c[b,h,r] = sum_d q_nope[b,h,d] Wuk[r,h,d]
    wkv = p["kv_up"]["w"].reshape(m.kv_lora_rank, h, m.qk_nope_dim + m.v_head_dim)
    w_uk, w_uv = wkv[..., : m.qk_nope_dim], wkv[..., m.qk_nope_dim:]
    # match the bf16 MXU convention of every other attention path (the
    # train-side flash attention computes scores/PV in bf16 too)
    cd = jnp.bfloat16
    q_c = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0].astype(jnp.float32),
                     w_uk.astype(jnp.float32))
    scale = softmax_scale(cfg)

    def scores(c, k_rope):
        return (
            jnp.einsum("bhr,bsr->bhs", q_c.astype(cd), c.astype(cd),
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bhd,bsd->bhs", q_rope.astype(cd), k_rope.astype(cd),
                         preferred_element_type=jnp.float32)
        ) * scale

    def weighted(e, c):
        return jnp.einsum("bhs,bsr->bhr", e.astype(cd), c.astype(cd),
                          preferred_element_type=jnp.float32)

    # the cache's earlier positions and the new token in one softmax (as
    # attention.decode_attention merges them)
    valid = A.decode_valid(cache["slot_pos"], pos)
    s = jnp.where(valid[None, None], scores(c_all, k_rope_all), -1e30)
    s_new = scores(c_new, k_rope_new)                           # (B, H, 1)
    mx = jnp.maximum(jnp.max(s, -1, keepdims=True), s_new)
    e = jnp.where(valid[None, None], jnp.exp(s - mx), 0.0)
    e_new = jnp.exp(s_new - mx)
    from repro import reduce as R

    denom = R.reduce(e, axis=-1, backend=R.backend_for_flags(cfg.mma_reductions))
    denom = jnp.maximum(denom + e_new[..., 0], 1e-30)[..., None]
    o_lat = weighted(e / denom, c_all) + weighted(e_new / denom, c_new)  # (B, H, R)
    out_h = jnp.einsum("bhr,rhd->bhd", o_lat, w_uv.astype(jnp.float32))
    out = P.dense_apply(p["o"], out_h.reshape(b, 1, -1).astype(x_t.dtype))
    return out, stored
