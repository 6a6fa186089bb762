"""Multi-head Latent Attention (DeepSeek-V2 / MiniCPM3-4B).

Q and KV are projected through low-rank latents; the KV cache stores only the
compressed latent ``c_kv`` (+ the shared RoPE key), which is MLA's memory
contribution. Decode re-expands K/V from the latent per step (the "weight
absorption" algebraic fusion is a further TPU optimization noted in
EXPERIMENTS.md; it does not change the contract).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import layers as L
from repro.models import params as P


def mla_init(key, cfg):
    m = cfg.mla
    d = cfg.d_model
    h = cfg.n_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    ks = P.split(key, 7)
    pq_d, aq_d = P.dense_init(ks[0], d, m.q_lora_rank, ("embed", None), cfg_dtype(cfg))
    pq_u, aq_u = P.dense_init(ks[1], m.q_lora_rank, h * qk, (None, "heads"), cfg_dtype(cfg))
    pkv_d, akv_d = P.dense_init(
        ks[2], d, m.kv_lora_rank + m.qk_rope_dim, ("embed", None), cfg_dtype(cfg)
    )
    pkv_u, akv_u = P.dense_init(
        ks[3], m.kv_lora_rank, h * (m.qk_nope_dim + m.v_head_dim), (None, "heads"), cfg_dtype(cfg)
    )
    po, ao = P.dense_init(ks[4], h * m.v_head_dim, d, ("heads", "embed"), cfg_dtype(cfg))
    qn, aqn = P.norm_init("rmsnorm", m.q_lora_rank, cfg_dtype(cfg))
    kvn, akvn = P.norm_init("rmsnorm", m.kv_lora_rank, cfg_dtype(cfg))
    return (
        {"q_down": pq_d, "q_up": pq_u, "kv_down": pkv_d, "kv_up": pkv_u,
         "o": po, "q_norm": qn, "kv_norm": kvn},
        {"q_down": aq_d, "q_up": aq_u, "kv_down": akv_d, "kv_up": akv_u,
         "o": ao, "q_norm": aqn, "kv_norm": akvn},
    )


def cfg_dtype(cfg):
    return jnp.dtype(cfg.dtype)


def _expand(p, x, positions, cfg):
    """Project x to per-head q, k, v (rope applied). Returns (q, k, v)."""
    m = cfg.mla
    h = cfg.n_heads
    b, s, _ = x.shape
    # Both latent norms are independent functions of x, so their statistics
    # batch into one segmented reduction pass (reduce_many; see
    # layers.rmsnorm_apply_many) -- one launch per layer instead of two.
    cq = P.dense_apply(p["q_down"], x)
    ckv_full = P.dense_apply(p["kv_down"], x)
    ckv_raw, k_rope = ckv_full[..., : m.kv_lora_rank], ckv_full[..., m.kv_lora_rank:]
    cq, ckv = L.rmsnorm_apply_many(
        (p["q_norm"], p["kv_norm"]),
        (cq, ckv_raw),
        eps=cfg.norm_eps,
        mma=cfg.mma_reductions,
    )
    q = P.dense_apply(p["q_up"], cq).reshape(b, s, h, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = L.rope(q_rope, positions, cfg.rope_theta)

    k_rope = L.rope(k_rope[:, :, None, :], positions, cfg.rope_theta)  # shared head
    kv = P.dense_apply(p["kv_up"], ckv).reshape(b, s, h, m.qk_nope_dim + m.v_head_dim)
    k_nope, v = kv[..., : m.qk_nope_dim], kv[..., m.qk_nope_dim:]
    k_rope_b = jnp.broadcast_to(k_rope, (b, s, h, m.qk_rope_dim))
    q_full = jnp.concatenate([q_nope, q_rope], -1)
    k_full = jnp.concatenate([k_nope, k_rope_b], -1)
    return q_full, k_full, v, ckv_full


def mla_train(p, x, positions, cfg):
    m = cfg.mla
    q, k, v, _ = _expand(p, x, positions, cfg)
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    out = A.flash_attention_xla(
        q, k, v, causal=True, mma=cfg.mma_reductions, sm_scale=scale
    )
    b, s, _, _ = out.shape
    return P.dense_apply(p["o"], out.reshape(b, s, -1))


def make_mla_cache(batch: int, s_max: int, cfg):
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, s_max, m.kv_lora_rank + m.qk_rope_dim), cfg_dtype(cfg)),
        "slot_pos": jnp.full((s_max,), -1, jnp.int32),
    }


def mla_fill_cache(p, x, positions, cache, cfg):
    """Prefill the compressed-latent cache. RoPE on the shared key is applied
    at *write* time (positions are absolute)."""
    m = cfg.mla
    ckv_full = P.dense_apply(p["kv_down"], x)
    k_rope = L.rope(
        ckv_full[..., m.kv_lora_rank:][:, :, None, :], positions, cfg.rope_theta
    )[:, :, 0, :]
    stored = jnp.concatenate([ckv_full[..., : m.kv_lora_rank], k_rope], -1)
    s = x.shape[1]
    ckv = jax.lax.dynamic_update_slice(cache["ckv"], stored, (0, 0, 0))
    slot_pos = cache["slot_pos"].at[:s].set(jnp.arange(s))
    return {"ckv": ckv, "slot_pos": slot_pos}


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
    (out, the token's new latent entry {"ckv": (B, 1, R + dr)}), which the
    caller writes at position ``pos``.
    """
    m = cfg.mla
    h = cfg.n_heads
    b = x_t.shape[0]
    posb = jnp.broadcast_to(pos, (b, 1))
    # query
    cq = P.dense_apply(p["q_down"], x_t)
    cq = L.norm_apply("rmsnorm", p["q_norm"], cq, eps=cfg.norm_eps, mma=cfg.mma_reductions)
    q = P.dense_apply(p["q_up"], cq).reshape(b, 1, h, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = L.rope(q_rope, posb, cfg.rope_theta)[:, 0]        # (B, H, dr)
    # this step's latent: attended beside the cache, written by the caller
    ckv_full = P.dense_apply(p["kv_down"], x_t)
    k_rope_t = L.rope(
        ckv_full[..., m.kv_lora_rank:][:, :, None, :], posb, cfg.rope_theta
    )[:, :, 0, :]
    stored = jnp.concatenate([ckv_full[..., : m.kv_lora_rank], k_rope_t], -1)
    # normalized latents + shared rope key: the cache's, read where they
    # lie, and the new token's
    def latents(ckv):
        c = L.norm_apply(
            "rmsnorm", p["kv_norm"], ckv[..., : m.kv_lora_rank],
            eps=cfg.norm_eps, mma=cfg.mma_reductions,
        )
        return c, ckv[..., m.kv_lora_rank:]

    c_all, k_rope_all = latents(cache["ckv"])                   # (B, S, R)
    c_new, k_rope_new = latents(stored)                         # (B, 1, R)
    # absorb W_uk into the query: q_c[b,h,r] = sum_d q_nope[b,h,d] Wuk[r,h,d]
    wkv = p["kv_up"]["w"].reshape(m.kv_lora_rank, h, m.qk_nope_dim + m.v_head_dim)
    w_uk, w_uv = wkv[..., : m.qk_nope_dim], wkv[..., m.qk_nope_dim:]
    # match the bf16 MXU convention of every other attention path (the
    # train-side flash attention computes scores/PV in bf16 too)
    cd = jnp.bfloat16
    q_c = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0].astype(jnp.float32),
                     w_uk.astype(jnp.float32))
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5

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
    return out, {"ckv": stored}
