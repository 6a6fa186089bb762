"""Attention: chunked flash-style (XLA), decode caches, GQA/MQA/local/cross.

Three execution paths, one contract (oracle: kernels.flash_attention.ref):
  * train/prefill: `flash_attention_xla` -- q and kv are tiled by lax.scan
    with an online softmax, O(Sq * kv_chunk) score memory. This is the path
    the multi-pod dry-run lowers (XLA:TPU fuses it; sub-quadratic memory is
    what makes prefill_32k compile within HBM).
  * TPU kernel: cfg.use_pallas routes to kernels.flash_attention (Pallas).
  * decode: cache-resident single-token attention; full cache for global
    attention, *ring buffer* cache for local (windowed) attention so
    long_500k holds O(window) state, not O(S). The cache is read where it
    lies, as (B, Hkv, S, D); the token's own key/value join it in the
    softmax, and the caller writes them at one position.

Softmax denominators ride the MXU via `layers.softmax_mma` / the MMA row-sum
inside the online update (the paper's eq. 9) when cfg.mma_reductions is on.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import reduce as R
from repro.models import layers as L
from repro.models import params as P

NEG = -1e30


# ------------------------------ projections ---------------------------------


def attn_init(key, d: int, n_heads: int, n_kv: int, d_head: int, dtype):
    ks = P.split(key, 4)
    q, aq = P.dense_init(ks[0], d, n_heads * d_head, ("embed", "heads"), dtype)
    k, ak = P.dense_init(ks[1], d, n_kv * d_head, ("embed", "kv_heads"), dtype)
    v, av = P.dense_init(ks[2], d, n_kv * d_head, ("embed", "kv_heads"), dtype)
    o, ao = P.dense_init(
        ks[3], n_heads * d_head, d, ("heads", "embed"), dtype, scale=(n_heads * d_head) ** -0.5
    )
    return {"q": q, "k": k, "v": v, "o": o}, {"q": aq, "k": ak, "v": av, "o": ao}


def _project_qkv(p, x, n_heads, n_kv, d_head):
    b, s, _ = x.shape
    q = P.dense_apply(p["q"], x).reshape(b, s, n_heads, d_head)
    k = P.dense_apply(p["k"], x).reshape(b, s, n_kv, d_head)
    v = P.dense_apply(p["v"], x).reshape(b, s, n_kv, d_head)
    return q, k, v


# ------------------------- chunked flash attention --------------------------


def _online_block(carry, qc, kc, vc, qpos, kpos, *, causal, window, kv_len, scale, mma):
    """One (q-chunk, kv-chunk) online-softmax update.

    qc: (B, Cq, Hkv, G, D); kc/vc: (B, Ck, Hkv, D).
    carry m/l: (B, Hkv, G, Cq); acc: (B, Hkv, G, Cq, D).
    """
    m, l, acc = carry
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk",
        qc.astype(jnp.bfloat16),
        kc.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    ) * scale
    mask = kpos[None, :] < kv_len
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= (qpos[:, None] - kpos[None, :]) < window
    s = jnp.where(mask[None, None, None], s, NEG)
    m_new = jnp.maximum(m, jnp.max(s, -1))
    e = jnp.exp(s - m_new[..., None])
    e = jnp.where(mask[None, None, None], e, 0.0)
    esum = R.reduce(e, axis=-1, backend=R.backend_for_flags(mma))
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + esum
    pv = jnp.einsum(
        "bhgqk,bkhd->bhgqd",
        e.astype(jnp.bfloat16),
        vc.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )
    acc_new = acc * alpha[..., None] + pv
    return m_new, l_new, acc_new


def flash_attention_xla(
    q: jax.Array,   # (B, Sq, H, D)
    k: jax.Array,   # (B, Skv, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    mma: bool = True,
    sm_scale: float | None = None,
) -> jax.Array:
    b, sq, h, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]  # MLA: value head dim may differ from qk head dim
    g = h // hkv
    scale = sm_scale if sm_scale is not None else d**-0.5
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    nq = -(-sq // q_chunk)
    nk = -(-skv // kv_chunk)
    sq_p, skv_p = nq * q_chunk, nk * kv_chunk
    qp = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, skv_p - skv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, skv_p - skv), (0, 0), (0, 0)))
    qg = qp.reshape(b, nq, q_chunk, hkv, g, d).swapaxes(0, 1)  # (nq, B, Cq, Hkv, G, D)
    kg = kp.reshape(b, nk, kv_chunk, hkv, d).swapaxes(0, 1)
    vg = vp.reshape(b, nk, kv_chunk, hkv, dv).swapaxes(0, 1)

    def per_q_chunk(_, qin):
        qc, iq = qin
        qpos = q_offset + iq * q_chunk + jnp.arange(q_chunk)

        # remat per KV chunk: the backward pass recomputes s/e tiles instead
        # of saving the O(S x S) score tensors (flash-attention's recompute
        # contract -- without this, bwd residuals are the full quadratic
        # attention matrix per layer; caught by dry-run memory_analysis).
        @jax.checkpoint
        def per_kv_chunk(carry, kin):
            kc, vc, ik = kin
            kpos = ik * kv_chunk + jnp.arange(kv_chunk)
            return (
                _online_block(
                    carry, qc, kc, vc, qpos, kpos,
                    causal=causal, window=window, kv_len=skv, scale=scale, mma=mma,
                ),
                None,
            )

        init = (
            jnp.full((b, hkv, g, q_chunk), NEG, jnp.float32),
            jnp.zeros((b, hkv, g, q_chunk), jnp.float32),
            jnp.zeros((b, hkv, g, q_chunk, dv), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(
            per_kv_chunk, init, (kg, vg, jnp.arange(nk))
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]          # (B,Hkv,G,Cq,Dv)
        return None, out.transpose(0, 3, 1, 2, 4)             # (B,Cq,Hkv,G,Dv)

    _, outs = jax.lax.scan(per_q_chunk, None, (qg, jnp.arange(nq)))
    out = outs.swapaxes(0, 1).reshape(b, sq_p, h, dv)[:, :sq]
    return out.astype(q.dtype)


# ------------------------------- decode -------------------------------------


def decode_attention(
    q: jax.Array,        # (B, 1, H, D) -- already RoPE'd
    k_cache: jax.Array,  # (B, Hkv, Smax, D) -- RoPE'd at write time
    v_cache: jax.Array,
    valid: jax.Array,    # (Smax,) bool: the cache positions to attend
    k_new: jax.Array | None = None,  # (B, Hkv, 1, D): the query's own key
    v_new: jax.Array | None = None,
    *,
    mma: bool = True,
    sm_scale: float | None = None,
) -> jax.Array:
    """Single-token attention over a cache read where it lies.

    The current token's own key and value (``k_new``/``v_new``), not yet in
    the cache, join the cache's valid positions in one softmax: one max,
    one sum, the same math as attending over a cache that already held
    them."""
    b, _, h, d = q.shape
    hkv = k_cache.shape[1]
    g = h // hkv
    scale = sm_scale if sm_scale is not None else d**-0.5
    qg = q.reshape(b, hkv, g, d).astype(jnp.bfloat16)
    s = jnp.einsum(
        "bhgd,bhsd->bhgs", qg, k_cache.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    ) * scale
    s = jnp.where(valid[None, None, None], s, NEG)
    m = jnp.max(s, -1, keepdims=True)
    if k_new is not None:
        s_new = jnp.einsum(
            "bhgd,bhsd->bhgs", qg, k_new.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        ) * scale
        m = jnp.maximum(m, s_new)
    e = jnp.where(valid[None, None, None], jnp.exp(s - m), 0.0)
    denom = R.reduce(e, axis=-1, backend=R.backend_for_flags(mma))
    out = jnp.einsum(
        "bhgs,bhsd->bhgd", e.astype(jnp.bfloat16),
        v_cache.astype(jnp.bfloat16), preferred_element_type=jnp.float32,
    )
    if k_new is not None:
        e_new = jnp.exp(s_new - m)
        denom = denom + e_new[..., 0]
        out = out + jnp.einsum(
            "bhgs,bhsd->bhgd", e_new.astype(jnp.bfloat16),
            v_new.astype(jnp.bfloat16), preferred_element_type=jnp.float32,
        )
    out = out / jnp.maximum(denom, 1e-30)[..., None]
    return out.reshape(b, 1, h, v_cache.shape[-1]).astype(q.dtype)


def decode_valid(slot_pos: jax.Array, pos: jax.Array, window: int | None = None):
    """The cache positions a decode query at ``pos`` attends besides its
    own: those written for an earlier position (``slot_pos`` is -1 where
    nothing was), and inside the window for local attention. Position
    ``pos`` itself is left out -- its key and value join from outside the
    cache -- so a cache that already holds it (a retried step's) is
    weighed as one that does not."""
    valid = (slot_pos >= 0) & (slot_pos < pos)
    if window is not None:
        valid &= (pos - slot_pos) < window
    return valid


# --------------------------- full attention blocks ---------------------------


def self_attention_train(p, x, positions, cfg, *, window=None):
    """(B, S, d) -> (B, S, d). Causal self-attention, train/prefill path."""
    q, k, v = _project_qkv(p, x, cfg.n_heads, cfg.n_kv_heads, cfg.d_head)
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    if cfg.use_pallas:
        from repro.kernels import flash_attention_diff

        out = flash_attention_diff(
            q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2), True, window, 0, None
        ).swapaxes(1, 2)
    else:
        out = flash_attention_xla(
            q, k, v, causal=True, window=window, mma=cfg.mma_reductions
        )
    b, s, _, _ = out.shape
    return P.dense_apply(p["o"], out.reshape(b, s, -1))


def cache_positions(s_max: int) -> int:
    """Positions a non-ring cache holds for a longest context of ``s_max``:
    rounded up to a multiple of 16, so the (positions, width) planes fill
    the chip's bf16 tiles of 16 rows with no padding. Otherwise the TPU
    compiler lays K/V out with the heads as the tiled rows, and every
    layer's slice is transposed for the attention dots. Positions past the
    last written stay empty (``slot_pos`` -1) and are never attended."""
    return -(-s_max // 16) * 16


def make_kv_cache(batch: int, s_max: int, n_kv: int, d_head: int, dtype):
    """Keys and values as (B, Hkv, Smax, D): each head's positions lie
    contiguous, as the decode dots read them."""
    return {
        "k": jnp.zeros((batch, n_kv, s_max, d_head), dtype),
        "v": jnp.zeros((batch, n_kv, s_max, d_head), dtype),
        "slot_pos": jnp.full((s_max,), -1, jnp.int32),
    }


def self_attention_decode(p, x_t, cache, pos, cfg, *, window=None):
    """One decode step. x_t: (B, 1, d); cache: full or ring (ring iff
    window), only read. Returns (out (B,1,d), the token's new cache entry
    {"k", "v"} of (B, Hkv, 1, D)); the caller writes it at slot
    ``pos % Smax`` -- for a ring cache (local attention, Smax == window)
    that evicts the oldest key."""
    b = x_t.shape[0]
    q, k, v = _project_qkv(p, x_t, cfg.n_heads, cfg.n_kv_heads, cfg.d_head)
    posb = jnp.broadcast_to(pos, (b, 1))
    q = L.rope(q, posb, cfg.rope_theta)
    k = L.rope(k, posb, cfg.rope_theta).swapaxes(1, 2)
    v = v.swapaxes(1, 2)
    out = decode_attention(
        q, cache["k"], cache["v"], decode_valid(cache["slot_pos"], pos, window),
        k, v, mma=cfg.mma_reductions,
    )
    out = P.dense_apply(p["o"], out.reshape(b, 1, -1))
    return out, {"k": k, "v": v}


def fill_kv_cache(p, x, positions, cache, cfg):
    """Prefill: project+rope the whole prompt into the cache (full caches;
    ring caches keep the last `window` positions)."""
    b, s, _ = x.shape
    _, k, v = _project_qkv(p, x, cfg.n_heads, cfg.n_kv_heads, cfg.d_head)
    k = L.rope(k, positions, cfg.rope_theta).swapaxes(1, 2)
    v = v.swapaxes(1, 2)
    s_max = cache["k"].shape[2]
    if s <= s_max:
        with jax.named_scope("kv_cache"):
            k_cache = jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0))
            v_cache = jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0))
            slot_pos = cache["slot_pos"].at[:s].set(jnp.arange(s))
    else:  # ring: keep the last s_max positions, each at slot pos % s_max so
        # later decode writes (slot = pos % s_max) evict oldest-first.
        tail = jnp.arange(s - s_max, s)
        perm = jnp.argsort(tail % s_max)  # perm[i] = tail index whose slot is i
        k_cache = k[:, :, -s_max:][:, :, perm]
        v_cache = v[:, :, -s_max:][:, :, perm]
        slot_pos = tail[perm].astype(jnp.int32)
    return {"k": k_cache, "v": v_cache, "slot_pos": slot_pos}


# ------------------------------ cross-attention ------------------------------


def cross_attention_init(key, d: int, n_heads: int, n_kv: int, d_head: int, dtype):
    p, a = attn_init(key, d, n_heads, n_kv, d_head, dtype)
    p["gate"] = jnp.zeros((), dtype)  # zero-init tanh gate (Llama-3.2-vision)
    a["gate"] = None
    return p, a


def cross_attention_apply(p, x, ctx, cfg):
    """x: (B, S, d) queries; ctx: (B, N, d) frontend embeddings (kv)."""
    b, s, _ = x.shape
    n = ctx.shape[1]
    q = P.dense_apply(p["q"], x).reshape(b, s, cfg.n_heads, cfg.d_head)
    k = P.dense_apply(p["k"], ctx).reshape(b, n, cfg.n_kv_heads, cfg.d_head)
    v = P.dense_apply(p["v"], ctx).reshape(b, n, cfg.n_kv_heads, cfg.d_head)
    out = flash_attention_xla(q, k, v, causal=False, mma=cfg.mma_reductions)
    out = P.dense_apply(p["o"], out.reshape(b, s, -1))
    return jnp.tanh(p["gate"].astype(jnp.float32)).astype(out.dtype) * out
