"""Model assembly: pattern-cycled decoder stack with scan-over-units.

A config's ``block_pattern`` (e.g. ("rec","rec","attn") for RecurrentGemma,
("attn","attn","attn","attn","xattn") for Llama-3.2-Vision) is cycled to
n_layers. Layers are grouped into *units* of one pattern period; unit params
are stacked on a leading axis and the stack is driven by ``lax.scan`` so the
HLO -- and the 512-device dry-run compile time -- stays flat in depth. A
partial tail unit (e.g. RecurrentGemma's 38 = 12*3 + 2) is applied unrolled,
and so are ``cfg.first_k_dense`` leading layers whose feed-forward is a
dense SwiGLU of ``d_ff`` where the others are MoE (DeepSeek-V2's dense
first layer): they sit outside the stacked units, under ``lead``, with
caches of their own.

Three entry points with one parameter tree:
  forward      (B, S) tokens -> logits           train / teacher-forcing
  prefill      builds every block's cache        inference phase 1
  decode_step  one token with caches             inference phase 2

Caches are per-kind pytrees (full KV, ring KV for local attention, compressed
latent for MLA, O(1) conv+state for SSM / RG-LRU) stacked exactly like the
params so the same scan drives them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import context as CTX
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import params as P
from repro.models import rglru as REC
from repro.models import ssm as SSM


# ------------------------------- blocks -------------------------------------


def _has_ffn(kind: str) -> bool:
    return kind in ("attn", "local_attn", "xattn", "rec")


def _ffn_init(key, cfg, dense=False):
    if cfg.moe is not None and not dense:
        return MOE.moe_init(key, cfg)
    return L.ffn_init(key, cfg.d_model, cfg.d_ff, cfg.ffn_kind, jnp.dtype(cfg.dtype))


def _ffn_apply(p, h, cfg, dense=False, dropless=False):
    """The block's feed-forward -> (y, metrics). A MoE layer routes with
    the training path's capacity dispatch, or with ``dropless`` (serving:
    prefill and decode) the dispatch that drops no token. A layer that
    holds only a share of the experts always routes dropless: the
    capacity dispatch computes every expert."""
    if cfg.moe is not None and not dense:
        if dropless or not cfg.moe.holds_all:
            return MOE.moe_apply_dropless(p, h, cfg)
        return MOE.moe_apply(p, h, cfg)
    return L.ffn_apply(p, h, cfg.ffn_kind), {}


def block_init(kind: str, key, cfg: ModelConfig, dense=False):
    ks = P.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    p, a = {}, {}
    p["norm1"], a["norm1"] = P.norm_init(cfg.norm, d, dt)
    if kind in ("attn", "local_attn"):
        if cfg.mla is not None:
            p["mix"], a["mix"] = MLA.mla_init(ks[0], cfg)
        else:
            p["mix"], a["mix"] = A.attn_init(
                ks[0], d, cfg.n_heads, cfg.n_kv_heads, cfg.d_head, dt
            )
    elif kind == "xattn":
        p["mix"], a["mix"] = A.cross_attention_init(
            ks[0], d, cfg.n_heads, cfg.n_kv_heads, cfg.d_head, dt
        )
    elif kind == "ssm":
        p["mix"], a["mix"] = SSM.ssm_init(ks[0], cfg)
    elif kind == "rec":
        p["mix"], a["mix"] = REC.rglru_init(ks[0], cfg)
    else:
        raise ValueError(kind)
    if _has_ffn(kind):
        p["norm2"], a["norm2"] = P.norm_init(cfg.norm, d, dt)
        p["ffn"], a["ffn"] = _ffn_init(ks[1], cfg, dense)
    return p, a


def _norm(p, h, cfg):
    return L.norm_apply(
        cfg.norm, p, h, eps=cfg.norm_eps, mma=cfg.mma_reductions,
        use_pallas=cfg.use_pallas,
    )


def block_train(kind, p, h, positions, cfg, ctx, dense=False):
    """One block, train/prefill compute. Returns (h, aux_loss_scalar)."""
    hn = _norm(p["norm1"], h, cfg)
    if kind in ("attn", "local_attn"):
        win = cfg.window if kind == "local_attn" else None
        if cfg.mla is not None:
            mix = MLA.mla_train(p["mix"], hn, positions, cfg)
        else:
            mix = A.self_attention_train(p["mix"], hn, positions, cfg, window=win)
    elif kind == "xattn":
        mix = A.cross_attention_apply(p["mix"], hn, ctx, cfg)
    elif kind == "ssm":
        mix = SSM.ssm_train(p["mix"], hn, cfg)
    elif kind == "rec":
        mix = REC.rglru_train(p["mix"], hn, cfg)
    h = h + mix
    aux = jnp.zeros((), jnp.float32)
    if _has_ffn(kind):
        y, metrics = _ffn_apply(p["ffn"], _norm(p["norm2"], h, cfg), cfg, dense)
        h = h + y
        aux = aux + sum(
            (v for k, v in metrics.items() if k in ("moe_aux", "moe_z")),
            jnp.zeros((), jnp.float32),
        )
    return h, aux


def block_make_cache(kind, batch, s_max, cfg):
    if kind in ("attn", "local_attn"):
        if cfg.mla is not None:
            return MLA.make_mla_cache(batch, A.cache_positions(s_max), cfg)
        if kind == "local_attn" and cfg.window and cfg.window < s_max:
            size = cfg.window  # ring: slot pos % window
        else:
            size = A.cache_positions(s_max)
        return A.make_kv_cache(batch, size, cfg.n_kv_heads, cfg.d_head, jnp.dtype(cfg.dtype))
    if kind == "xattn":
        shape = (batch, cfg.n_kv_heads, cfg.n_img_tokens, cfg.d_head)
        return {"k": jnp.zeros(shape, jnp.dtype(cfg.dtype)),
                "v": jnp.zeros(shape, jnp.dtype(cfg.dtype))}
    if kind == "ssm":
        return SSM.make_ssm_cache(batch, cfg)
    if kind == "rec":
        return REC.make_rglru_cache(batch, cfg)
    raise ValueError(kind)


def block_fill_cache(kind, p, h, positions, cache, cfg, ctx, dense=False):
    """Prefill: run the block AND populate its cache. Returns (h, aux, cache).

    The mixer input is norm1(h); caches are filled from exactly that stream,
    and SSM / RG-LRU thread their true final recurrent state out of the
    train-path scan (exact prefill->decode handoff, verified by
    tests/test_serving_consistency.py)."""
    hn = _norm(p["norm1"], h, cfg)
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn", "local_attn"):
        win = cfg.window if kind == "local_attn" else None
        if cfg.mla is not None:
            cache = MLA.mla_fill_cache(p["mix"], hn, positions, cache, cfg)
            mix = MLA.mla_train(p["mix"], hn, positions, cfg)
        else:
            cache = A.fill_kv_cache(p["mix"], hn, positions, cache, cfg)
            mix = A.self_attention_train(p["mix"], hn, positions, cfg, window=win)
    elif kind == "xattn":
        b, n = ctx.shape[0], ctx.shape[1]
        k = P.dense_apply(p["mix"]["k"], ctx).reshape(b, n, cfg.n_kv_heads, cfg.d_head)
        v = P.dense_apply(p["mix"]["v"], ctx).reshape(b, n, cfg.n_kv_heads, cfg.d_head)
        cache = {"k": k.swapaxes(1, 2), "v": v.swapaxes(1, 2)}
        mix = A.cross_attention_apply(p["mix"], hn, ctx, cfg)
    elif kind == "ssm":
        mix, cache = SSM.ssm_train(p["mix"], hn, cfg, return_state=True)
    elif kind == "rec":
        mix, cache = REC.rglru_train(p["mix"], hn, cfg, return_state=True)
    else:
        raise ValueError(kind)
    h = h + mix
    if _has_ffn(kind):
        y, metrics = _ffn_apply(p["ffn"], _norm(p["norm2"], h, cfg), cfg,
                                dense, dropless=True)
        h = h + y
        aux = aux + sum(
            (v for k, v in metrics.items() if k in ("moe_aux", "moe_z")),
            jnp.zeros((), jnp.float32),
        )
    return h, aux, cache


def block_decode(kind, p, h, cache, pos, cfg, ctx, dense=False):
    """One block, one token. Returns (h, new, stats): ``new`` is the token's
    entry of a position-indexed cache (full K/V, ring K/V, MLA latent), only
    read here and written by ``decode_step``; the whole new state of a
    recurrent cache (SSM, RG-LRU); None for a cross-attention cache, which
    decode only reads. ``stats`` holds a MoE layer's ``moe_held_pairs``
    (empty for any other layer)."""
    hn = _norm(p["norm1"], h, cfg)
    if kind in ("attn", "local_attn"):
        win = cfg.window if kind == "local_attn" else None
        if cfg.mla is not None:
            mix, new = MLA.mla_decode(p["mix"], hn, cache, pos, cfg)
        else:
            mix, new = A.self_attention_decode(p["mix"], hn, cache, pos, cfg, window=win)
    elif kind == "xattn":
        q = P.dense_apply(p["mix"]["q"], hn).reshape(
            hn.shape[0], 1, cfg.n_heads, cfg.d_head
        )
        n = cache["k"].shape[2]
        out = A.decode_attention(
            q, cache["k"], cache["v"], jnp.ones((n,), bool),
            mma=cfg.mma_reductions,
        )
        mix = P.dense_apply(p["mix"]["o"], out.reshape(hn.shape[0], 1, -1))
        mix = jnp.tanh(p["mix"]["gate"].astype(jnp.float32)).astype(mix.dtype) * mix
        new = None
    elif kind == "ssm":
        mix, new = SSM.ssm_decode(p["mix"], hn, cache, cfg)
    elif kind == "rec":
        mix, new = REC.rglru_decode(p["mix"], hn, cache, cfg)
    h = h + mix
    stats = {}
    if _has_ffn(kind):
        y, metrics = _ffn_apply(p["ffn"], _norm(p["norm2"], h, cfg), cfg,
                                dense, dropless=True)
        h = h + y
        stats = {k: v for k, v in metrics.items() if k == "moe_held_pairs"}
    return h, new, stats


# ------------------------------ full model ----------------------------------


def _pattern_units(cfg: ModelConfig):
    """(pattern, stacked units, tail kinds) of the layers after the
    ``first_k_dense`` leading ones."""
    pat = tuple(cfg.block_pattern)
    n = cfg.n_layers - cfg.first_k_dense
    n_units = n // len(pat)
    tail = tuple(pat[: n % len(pat)])
    return pat, n_units, tail


def _lead_kinds(cfg: ModelConfig):
    """The kinds of the leading dense layers (the pattern's first ones)."""
    return cfg.pattern_layers[: cfg.first_k_dense]


def init_params(key, cfg: ModelConfig):
    """Returns (params, axes). Unit params stacked for lax.scan."""
    pat, n_units, tail = _pattern_units(cfg)
    ks = P.split(key, 6)
    dt = jnp.dtype(cfg.dtype)
    params, axes = {}, {}
    nbooks = max(1, cfg.n_codebooks)
    if cfg.n_codebooks:
        tbl = (jax.random.normal(ks[0], (nbooks, cfg.vocab_size, cfg.d_model), jnp.float32)
               * cfg.d_model**-0.5).astype(dt)
        params["embed"] = {"table": tbl}
        axes["embed"] = {"table": (None, "vocab", "embed")}
    else:
        params["embed"], axes["embed"] = P.embed_init(
            ks[0], cfg.vocab_size, cfg.d_model, dt
        )

    def unit_init(k):
        kks = P.split(k, len(pat))
        ps, as_ = {}, {}
        for i, kind in enumerate(pat):
            ps[f"pos{i}"], as_[f"pos{i}"] = block_init(kind, kks[i], cfg)
        return ps, as_

    lead = _lead_kinds(cfg)
    if lead:
        lp, la = {}, {}
        lks = P.split(ks[4], len(lead))
        for i, kind in enumerate(lead):
            lp[f"pos{i}"], la[f"pos{i}"] = block_init(kind, lks[i], cfg,
                                                      dense=True)
        params["lead"], axes["lead"] = lp, la
    params["units"], axes["units"] = P.stack_init(unit_init, ks[1], n_units)
    if tail:
        tp, ta = {}, {}
        tks = P.split(ks[2], len(tail))
        for i, kind in enumerate(tail):
            tp[f"pos{i}"], ta[f"pos{i}"] = block_init(kind, tks[i], cfg)
        params["tail"], axes["tail"] = tp, ta
    params["final_norm"], axes["final_norm"] = P.norm_init(cfg.norm, cfg.d_model, dt)
    if not cfg.tie_embeddings:
        nv = P.padded_vocab(cfg.vocab_size)
        if cfg.n_codebooks:
            head = (jax.random.normal(
                ks[3], (nbooks, cfg.d_model, nv), jnp.float32
            ) * cfg.d_model**-0.5).astype(dt)
            params["head"] = {"w": head}
            axes["head"] = {"w": (None, None, "vocab")}
        else:
            # d_model dim NOT FSDP-sharded (see params.embed_init note)
            params["head"], axes["head"] = P.dense_init(
                ks[3], cfg.d_model, nv, (None, "vocab"), dt
            )
    return params, axes


def _embed(params, cfg, tokens):
    if cfg.n_codebooks:
        # (B, S, K) codebook streams summed (MusicGen-style input fusion)
        tbl = params["embed"]["table"]
        parts = [tbl[k][tokens[..., k]] for k in range(cfg.n_codebooks)]
        return functools.reduce(jnp.add, parts)
    return params["embed"]["table"][tokens]


def _mask_pad_logits(logits, cfg):
    """Vocab rows are padded for sharding (params.padded_vocab); pad logits
    are masked so softmax/CE/argmax are exactly the unpadded math."""
    nv = logits.shape[-1]
    if nv == cfg.vocab_size:
        return logits
    pad_mask = jnp.arange(nv) >= cfg.vocab_size
    return jnp.where(pad_mask, -1e30, logits)


def _head(params, cfg, h):
    if cfg.tie_embeddings:
        logits = jnp.einsum(
            "bsd,vd->bsv", h.astype(jnp.float32),
            params["embed"]["table"].astype(jnp.float32),
        )
    elif cfg.n_codebooks:
        logits = jnp.einsum(
            "bsd,kdv->bskv", h.astype(jnp.float32),
            params["head"]["w"].astype(jnp.float32),
        )
    else:
        logits = jnp.einsum(
            "bsd,dv->bsv", h.astype(jnp.float32),
            params["head"]["w"].astype(jnp.float32),
        )
    if cfg.logits_softcap:
        c = cfg.logits_softcap
        logits = jnp.tanh(logits / c) * c
    return _mask_pad_logits(logits, cfg)


def _head_public(params, cfg, h):
    """Public logits contract: exactly vocab_size entries. The chunked loss
    keeps the padded (masked) form to avoid resharding per chunk."""
    return _head(params, cfg, h)[..., : cfg.vocab_size]


def forward_hidden(params, cfg: ModelConfig, tokens, ctx=None):
    """Backbone forward to the final normed hidden state (no head projection
    -- the chunked loss applies the head per seq tile). -> (h, aux)."""
    pat, n_units, tail = _pattern_units(cfg)
    h = CTX.constrain(_embed(params, cfg, tokens))
    b, s = h.shape[0], h.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    aux0 = jnp.zeros((), jnp.float32)
    for i, kind in enumerate(_lead_kinds(cfg)):
        h, a = block_train(kind, params["lead"][f"pos{i}"], h, positions, cfg,
                           ctx, dense=True)
        h = CTX.constrain(h)
        aux0 = aux0 + a

    def unit_fn(carry, unit_params):
        hh, aux = carry
        for i, kind in enumerate(pat):
            hh, a = block_train(kind, unit_params[f"pos{i}"], hh, positions, cfg, ctx)
            hh = CTX.constrain(hh)
            aux = aux + a
        return (hh, aux), None

    body = jax.checkpoint(unit_fn) if cfg.remat else unit_fn
    (h, aux), _ = jax.lax.scan(body, (h, aux0), params["units"])
    for i, kind in enumerate(tail):
        h, a = block_train(kind, params["tail"][f"pos{i}"], h, positions, cfg, ctx)
        h = CTX.constrain(h)
        aux = aux + a
    h = _norm(params["final_norm"], h, cfg)
    return h, aux


def forward(params, cfg: ModelConfig, tokens, ctx=None):
    """Teacher-forcing forward. tokens: (B, S) or (B, S, K). -> (logits, aux)."""
    h, aux = forward_hidden(params, cfg, tokens, ctx)
    return _head_public(params, cfg, h), aux


def make_caches(cfg: ModelConfig, batch: int, s_max: int):
    pat, n_units, tail = _pattern_units(cfg)

    def unit_cache(_):
        return {
            f"pos{i}": block_make_cache(kind, batch, s_max, cfg)
            for i, kind in enumerate(pat)
        }

    units = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_units,) + x.shape).copy()
        if n_units else x[None][:0],
        unit_cache(None),
    )
    caches = {"units": units}
    if _lead_kinds(cfg):
        caches["lead"] = {
            f"pos{i}": block_make_cache(kind, batch, s_max, cfg)
            for i, kind in enumerate(_lead_kinds(cfg))
        }
    if tail:
        caches["tail"] = {
            f"pos{i}": block_make_cache(kind, batch, s_max, cfg)
            for i, kind in enumerate(tail)
        }
    return caches


@jax.named_scope("kv_cache")
def _cache_layer(stacked, i):
    """Layer ``i``'s caches, sliced from the stacked caches."""
    return jax.tree.map(
        lambda c: jax.lax.dynamic_index_in_dim(c, i, 0, keepdims=False),
        stacked,
    )


@jax.named_scope("kv_cache")
def _cache_set_layer(stacked, new_cache, i):
    """The stacked caches with layer ``i``'s replaced by ``new_cache``."""
    return jax.tree.map(
        lambda c, nc: jax.lax.dynamic_update_index_in_dim(
            c, nc.astype(c.dtype), i, 0
        ),
        stacked,
        new_cache,
    )


def prefill(params, cfg: ModelConfig, tokens, caches, ctx=None):
    """Run the prompt, filling caches. Returns (last-token logits, caches)."""
    pat, n_units, tail = _pattern_units(cfg)
    h = _embed(params, cfg, tokens)
    b, s = h.shape[0], h.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    lead = {}
    for i, kind in enumerate(_lead_kinds(cfg)):
        h, _, lead[f"pos{i}"] = block_fill_cache(
            kind, params["lead"][f"pos{i}"], h, positions,
            caches["lead"][f"pos{i}"], cfg, ctx, dense=True,
        )

    def unit_fn(carry, xs):
        hh, aux, stacked = carry
        unit_params, i = xs
        unit_cache = _cache_layer(stacked, i)
        new_cache = {}
        for j, kind in enumerate(pat):
            hh, a, new_cache[f"pos{j}"] = block_fill_cache(
                kind, unit_params[f"pos{j}"], hh, positions,
                unit_cache[f"pos{j}"], cfg, ctx,
            )
            hh = CTX.constrain(hh)
            aux = aux + a
        stacked = _cache_set_layer(stacked, new_cache, i)
        return (hh, aux, stacked), None

    (h, _, new_units), _ = jax.lax.scan(
        unit_fn, (h, jnp.zeros((), jnp.float32), caches["units"]),
        (params["units"], jnp.arange(n_units)),
    )
    out_caches = {"units": new_units}
    if lead:
        out_caches["lead"] = lead
    if tail:
        tc = {}
        for i, kind in enumerate(tail):
            h, _, tc[f"pos{i}"] = block_fill_cache(
                kind, params["tail"][f"pos{i}"], h, positions,
                caches["tail"][f"pos{i}"], cfg, ctx,
            )
        out_caches["tail"] = tc
    h = _norm(params["final_norm"], h, cfg)
    return _head_public(params, cfg, h[:, -1:]), out_caches


def is_position_indexed(cache) -> bool:
    """A block cache indexed by position (full K/V, ring K/V, MLA latent):
    it carries ``slot_pos``, the position each slot holds."""
    return "slot_pos" in cache


def split_caches(caches):
    """(position-indexed caches, the rest), each a caches tree of its own
    blocks. A decode step writes one position of the first in place; the
    rest it replaces (recurrent states) or only reads (cross-attention)."""
    def part(keep):
        return {grp: {k: c for k, c in blocks.items() if is_position_indexed(c) == keep}
                for grp, blocks in caches.items()}

    return part(True), part(False)


def merge_caches(indexed, rest):
    """The inverse of ``split_caches``."""
    return {grp: {**indexed[grp], **rest[grp]} for grp in indexed}


@jax.named_scope("kv_cache")
def _write_position(cache, new, pos):
    """A position-indexed block cache with ``new`` -- one entry per data
    leaf, every layer's when stacked -- at slot ``pos % Smax`` of its
    position axis (the second-to-last of each data leaf), and ``pos`` in
    ``slot_pos``: one ``dynamic_update_slice`` per leaf, in place when the
    cache is donated."""
    slot = pos % cache["slot_pos"].shape[-1]
    out = {}
    for key, c in cache.items():
        if key == "slot_pos":
            x, axis = jnp.full(c.shape[:-1] + (1,), pos, c.dtype), c.ndim - 1
        else:
            x, axis = new[key].astype(c.dtype), c.ndim - 2
        start = [0] * c.ndim
        start[axis] = slot
        out[key] = jax.lax.dynamic_update_slice(c, x, start)
    return out


def clear_position(caches, pos):
    """``caches`` with zeros at position ``pos`` of every position-indexed
    block cache, one ``dynamic_update_slice`` per leaf (in place when the
    caches are donated). A decode step at ``pos`` weighs that position 0
    but its dots still multiply it, and 0 * NaN is NaN: after clearing,
    the step reads the same as it did before any attempt of it wrote
    there."""
    def clear(cache):
        zeros = {k: jnp.zeros(c.shape[:-2] + (1,) + c.shape[-1:], c.dtype)
                 for k, c in cache.items() if k != "slot_pos"}
        return _write_position(cache, zeros, pos)

    return {grp: {k: clear(c) if is_position_indexed(c) else c
                  for k, c in blocks.items()}
            for grp, blocks in caches.items()}


def decode_step(params, cfg: ModelConfig, token_t, caches, pos, ctx=None,
                with_stats=False):
    """One token step. token_t: (B, 1) or (B, 1, K); pos: scalar int32.
    Returns (logits (B,1,...), new_caches), and with ``with_stats`` a third
    item: the step's counters summed over layers (``moe_held_pairs``, the
    routed pairs computed on this device's experts, for a MoE config;
    empty otherwise).

    The layer scan carries only the hidden state. The stacked caches enter
    it as ``xs``, read where they lie: each layer attends over its cache's
    positions before ``pos`` and the token's own key/value, which it
    returns as its small ``ys`` (one position, (B, Hkv, 1, D) a layer);
    recurrent states come back whole, as ``ys`` of their own O(B * state)
    size, and cross-attention caches are only read. After the scan each
    position-indexed leaf gets every layer's new entry, and ``slot_pos``
    its ``pos``, in one ``dynamic_update_slice`` at position ``pos``
    (``_write_position``). No layer's cache is sliced out or written back
    and no whole cache is double-buffered: with the caches donated
    (``launch.serve``) the write lands in place, and the step touches no
    position but ``pos``. Position ``pos`` is never attended from the
    cache, so a step re-run on caches an earlier attempt of it wrote into
    (the runtime's retry) reads what the first attempt read, once
    ``clear_position`` has zeroed what that attempt left there."""
    pat, n_units, tail = _pattern_units(cfg)
    h = _embed(params, cfg, token_t)
    stats = []

    def write(cache, new):
        if is_position_indexed(cache):
            return _write_position(cache, new, pos)
        return cache if new is None else new

    out_caches = {}
    if _lead_kinds(cfg):
        lc = {}
        for i, kind in enumerate(_lead_kinds(cfg)):
            c = caches["lead"][f"pos{i}"]
            h, new, st = block_decode(kind, params["lead"][f"pos{i}"], h, c,
                                      pos, cfg, ctx, dense=True)
            lc[f"pos{i}"] = write(c, new)
            stats.append(st)
        out_caches["lead"] = lc

    def unit_fn(hh, xs):
        unit_params, unit_cache = xs
        new, st = {}, []
        for j, kind in enumerate(pat):
            hh, new[f"pos{j}"], s = block_decode(
                kind, unit_params[f"pos{j}"], hh, unit_cache[f"pos{j}"], pos, cfg, ctx
            )
            hh = CTX.constrain(hh)
            st.append(s)
        return hh, (new, st)

    h, (new_units, unit_stats) = jax.lax.scan(
        unit_fn, h, (params["units"], caches["units"]))
    stats.extend(jax.tree.map(jnp.sum, unit_stats))
    out_caches["units"] = {
        k: write(c, new_units[k]) for k, c in caches["units"].items()
    }
    if tail:
        tc = {}
        for i, kind in enumerate(tail):
            c = caches["tail"][f"pos{i}"]
            h, new, st = block_decode(kind, params["tail"][f"pos{i}"], h, c,
                                      pos, cfg, ctx)
            tc[f"pos{i}"] = write(c, new)
            stats.append(st)
        out_caches["tail"] = tc
    h = _norm(params["final_norm"], h, cfg)
    logits = _head_public(params, cfg, h)
    if not with_stats:
        return logits, out_caches
    total = {}
    for st in stats:
        for k, v in st.items():
            total[k] = total.get(k, 0) + v
    return logits, out_caches, total
