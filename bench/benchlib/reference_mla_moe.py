"""The plain reference of a DeepSeek-V2 decoder, written from the published
``modeling_deepseek.py`` and sharing no code with the program: embedding;
pre-norm blocks of multi-head latent attention (a direct query projection,
the key/value latent normalised and expanded per head, the rope dims
rotated at YaRN's frequencies with the softmax scaled by YaRN's factor);
a dense SwiGLU feed-forward in the leading layers and in the others a
softmax router over every routed expert, its top-k gates (renormalised or
scaled as configured), the routed experts' SwiGLUs and the shared experts'
one SwiGLU; a final RMSNorm and an untied head.

Everything is float32, every matrix product at ``HIGHEST`` precision,
layer by layer over whole sequences: no cache, kernel or batching of
requests. ``prec="fp8"`` is the control of ``benchlib.reference``: every
matrix product's operands rounded to float8.

Departures from the published model, each one the program's too:

- Only the experts this chip holds (``moe.held_start`` on, ``moe.n_held``
  of them) add to a layer's output; a pair routed to any other expert adds
  nothing. The router still scores every routed expert, and the top-k is
  taken over all of them.
- Each held expert runs densely over every token and is weighted by its
  gate where it was chosen, 0 elsewhere: the same sum as a dispatch.
- Weights are the benchmark's (``benchlib.weights``), made from the seed
  in the program's parameter layout, and held in their served bfloat16
  (the router in float32), widened to float32 inside each layer.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import weights
from benchlib.reference import VOCAB_ROWS, make_ein

HI = jax.lax.Precision.HIGHEST

# the program's parameter paths, by the reference's short names; ``{g}``
# is the layer's group: ``['lead']['pos0']`` or ``['units']['pos0']``
ATTN = {
    "n1": "{g}['norm1']['scale']",
    "q": "{g}['mix']['q']['w']",
    "kv_down": "{g}['mix']['kv_down']['w']",
    "kv_norm": "{g}['mix']['kv_norm']['scale']",
    "kv_up": "{g}['mix']['kv_up']['w']",
    "o": "{g}['mix']['o']['w']",
    "n2": "{g}['norm2']['scale']",
}
DENSE = {
    "gate": "{g}['ffn']['gate']['w']",
    "up": "{g}['ffn']['up']['w']",
    "down": "{g}['ffn']['down']['w']",
}
MOE = {
    "router": "{g}['ffn']['router']",
    "e_gate": "{g}['ffn']['gate']",
    "e_up": "{g}['ffn']['up']",
    "e_down": "{g}['ffn']['down']",
    "s_gate": "{g}['ffn']['shared']['gate']['w']",
    "s_up": "{g}['ffn']['shared']['up']['w']",
    "s_down": "{g}['ffn']['shared']['down']['w']",
}
TOP = {
    "embed": "['embed']['table']",
    "fn": "['final_norm']['scale']",
    "head": "['head']['w']",
}
LEAD = "['lead']['pos{i}']"
UNIT = "['units']['pos0']"


# ---- the published formulas ------------------------------------------------

def yarn_get_mscale(scale: float, mscale: float) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_find_correction_dim(num_rotations, dim, base, max_pos) -> float:
    return (dim * math.log(max_pos / (num_rotations * 2 * math.pi))) / (
        2 * math.log(base))


def yarn_find_correction_range(low_rot, high_rot, dim, base, max_pos):
    low = math.floor(yarn_find_correction_dim(low_rot, dim, base, max_pos))
    high = math.ceil(yarn_find_correction_dim(high_rot, dim, base, max_pos))
    return max(low, 0), min(high, dim - 1)


def yarn_linear_ramp_mask(lo: float, hi: float, dim: int) -> np.ndarray:
    if lo == hi:
        hi += 0.001
    return np.clip((np.arange(dim, dtype=np.float32) - lo) / (hi - lo),
                   0.0, 1.0)


def inv_freq(m: dict) -> tuple:
    """(inverse frequencies of the rope dims, cos/sin scale) as
    ``DeepseekV2YarnRotaryEmbedding`` computes them."""
    dim, base = m["mla"]["qk_rope_dim"], float(m["rope_theta"])
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    y = m.get("rope_scaling")
    if not y:
        return extra.astype(np.float32), 1.0
    inter = 1.0 / (y["factor"] * base ** (np.arange(0, dim, 2,
                                                     dtype=np.float32) / dim))
    lo, hi = yarn_find_correction_range(y["beta_fast"], y["beta_slow"], dim,
                                        base, y["original_max_position"])
    mask = 1.0 - yarn_linear_ramp_mask(lo, hi, dim // 2)
    inv = inter * (1 - mask) + extra * mask
    scale = (yarn_get_mscale(y["factor"], y["mscale"])
             / yarn_get_mscale(y["factor"], y["mscale_all_dim"]))
    return inv.astype(np.float32), float(scale)


def softmax_scale(m: dict) -> float:
    a = m["mla"]
    scale = (a["qk_nope_dim"] + a["qk_rope_dim"]) ** -0.5
    y = m.get("rope_scaling")
    if y and y.get("mscale_all_dim"):
        ms = yarn_get_mscale(y["factor"], y["mscale_all_dim"])
        scale = scale * ms * ms
    return scale


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rotate_half(x):
    h = x.shape[-1] // 2
    return jnp.concatenate([-x[..., h:], x[..., :h]], -1)


def apply_rope(x, m: dict):
    """x: (B, S, H, dr) at positions 0..S-1. Interleaved pairs are
    permuted to rotate-half order first, as ``apply_rotary_pos_emb`` does."""
    b, s, h, d = x.shape
    if m["mla"].get("rope_interleave"):
        x = x.reshape(b, s, h, d // 2, 2).swapaxes(-1, -2).reshape(b, s, h, d)
    inv, mscale = inv_freq(m)
    t = jnp.arange(s, dtype=jnp.float32)
    freqs = t[:, None] * jnp.asarray(inv)[None, :]
    emb = jnp.concatenate([freqs, freqs], -1)
    cos = (jnp.cos(emb) * mscale)[None, :, None, :]
    sin = (jnp.sin(emb) * mscale)[None, :, None, :]
    return x * cos + rotate_half(x) * sin


# ---- one layer ---------------------------------------------------------------

def attention(m: dict, ein, p: dict, x):
    """DeepseekV2Attention without q-LoRA on normed x: (B, S, d)."""
    a = m["mla"]
    b, s, _ = x.shape
    nh, dn, dr, dv = m["n_heads"], a["qk_nope_dim"], a["qk_rope_dim"], \
        a["v_head_dim"]
    r = a["kv_lora_rank"]
    q = ein("bsd,dk->bsk", x, p["q"]).reshape(b, s, nh, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    ckv = ein("bsd,dk->bsk", x, p["kv_down"])
    c, k_pe = ckv[..., :r], ckv[..., r:]
    c = rms(c, p["kv_norm"], m["norm_eps"])
    kv = ein("bsr,rk->bsk", c, p["kv_up"]).reshape(b, s, nh, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_pe = apply_rope(q_pe, m)
    k_pe = apply_rope(k_pe[:, :, None, :], m)
    qf = jnp.concatenate([q_nope, q_pe], -1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (b, s, nh, dr))],
                         -1)
    sc = ein("bqhd,bkhd->bhqk", qf, kf) * softmax_scale(m)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    att = jax.nn.softmax(sc, axis=-1)
    o = ein("bhqk,bkhd->bqhd", att, v).reshape(b, s, nh * dv)
    return ein("bsk,kd->bsd", o, p["o"])


def swiglu(ein, g, u, dn, x):
    return ein("bsf,fd->bsd", jax.nn.silu(ein("bsd,df->bsf", x, g))
               * ein("bsd,df->bsf", x, u), dn)


def moe(m: dict, ein, p: dict, x):
    """DeepseekV2MoE on normed x, over the held experts only."""
    e = m["moe"]
    logits = ein("bsd,de->bse", x, p["router"])
    scores = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(scores, e["top_k"])
    if e.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    else:
        w = w * e.get("routed_scaling_factor", 1.0)
    y = jnp.zeros_like(x)
    start = e.get("held_start", 0)
    for j in range(p["e_gate"].shape[0]):
        gate = jnp.sum(jnp.where(idx == start + j, w, 0.0), -1)   # (B, S)
        y = y + gate[..., None] * swiglu(ein, p["e_gate"][j], p["e_up"][j],
                                         p["e_down"][j], x)
    if "s_gate" in p:
        y = y + swiglu(ein, p["s_gate"], p["s_up"], p["s_down"], x)
    return y


def route(m: dict, ein, p: dict, h):
    """The top-k experts a MoE block's router picks for h: (B, S, k)."""
    eps = m["norm_eps"]
    h = h + attention(m, ein, p, rms(h, p["n1"], eps))
    logits = ein("bsd,de->bse", rms(h, p["n2"], eps), p["router"])
    return jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                         m["moe"]["top_k"])[1]


def block(m: dict, ein, p: dict, h, dense: bool):
    eps = m["norm_eps"]
    h = h + attention(m, ein, p, rms(h, p["n1"], eps))
    x = rms(h, p["n2"], eps)
    if dense:
        return h + swiglu(ein, p["gate"], p["up"], p["down"], x)
    return h + moe(m, ein, p, x)


# ---- the weights ------------------------------------------------------------

def leaf_shapes(m: dict) -> dict:
    """``{path: ShapeDtypeStruct}`` in the program's layout, from the
    configuration alone: leading dense layers unstacked, the others
    stacked, vocabulary rows padded, the router in float32."""
    a, e = m["mla"], m["moe"]
    d, nh, lead = m["d_model"], m["n_heads"], m.get("first_k_dense", 0)
    nl = m["n_layers"] - lead
    held = e.get("held_count") or e["n_experts"]
    fe = e["d_ff_expert"]
    rows = -(-m["vocab_size"] // VOCAB_ROWS) * VOCAB_ROWS
    dt, f32 = jnp.dtype(m["dtype"]), jnp.dtype(jnp.float32)
    attn = {"n1": (d,), "q": (d, nh * (a["qk_nope_dim"] + a["qk_rope_dim"])),
            "kv_down": (d, a["kv_lora_rank"] + a["qk_rope_dim"]),
            "kv_norm": (a["kv_lora_rank"],),
            "kv_up": (a["kv_lora_rank"],
                      nh * (a["qk_nope_dim"] + a["v_head_dim"])),
            "o": (nh * a["v_head_dim"], d), "n2": (d,)}
    dense = {"gate": (d, m["d_ff"]), "up": (d, m["d_ff"]),
             "down": (m["d_ff"], d)}
    ffn = {"router": (d, e["n_experts"]), "e_gate": (held, d, fe),
           "e_up": (held, d, fe), "e_down": (held, fe, d)}
    shared = e.get("n_shared_experts", 0) * fe
    if shared:
        ffn.update({"s_gate": (d, shared), "s_up": (d, shared),
                    "s_down": (shared, d)})
    out = {}
    for i in range(lead):
        g = LEAD.format(i=i)
        for name, shape in {**attn, **dense}.items():
            path = {**ATTN, **DENSE}[name].format(g=g)
            out[path] = jax.ShapeDtypeStruct(shape, dt)
    for name, shape in {**attn, **ffn}.items():
        path = {**ATTN, **MOE}[name].format(g=UNIT)
        out[path] = jax.ShapeDtypeStruct(
            (nl,) + shape, f32 if name == "router" else dt)
    out[TOP["embed"]] = jax.ShapeDtypeStruct((rows, d), dt)
    out[TOP["fn"]] = jax.ShapeDtypeStruct((d,), dt)
    out[TOP["head"]] = jax.ShapeDtypeStruct((d, rows), dt)
    return out


_MAKERS: dict = {}


def _make(key, path: str, s, layer):
    sig = (path, s.shape, str(s.dtype), layer is None)
    if sig not in _MAKERS:
        _MAKERS[sig] = jax.jit(
            lambda k, i: weights.layer_of(k, path, s.shape, s.dtype, i),
            static_argnums=() if layer is not None else (1,))
    return _MAKERS[sig](key, layer)


class ServeReference:
    """Teacher-forced logits of the plain model over whole sequences, the
    weights regenerated from the seed and held in their served dtype."""

    def __init__(self, cfg: dict, key, prec="f32"):
        m = cfg
        self.m = m
        shapes = leaf_shapes(m)
        lead = m.get("first_k_dense", 0)
        names = {**ATTN, **DENSE}

        def layer(g, table, i):
            out = {}
            for name, path in table.items():
                p = path.format(g=g)
                if p in shapes:
                    out[name] = _make(key, p, shapes[p], i)
            return out

        self.lead = [layer(LEAD.format(i=i), names, None)
                     for i in range(lead)]
        self.units = [layer(UNIT, {**ATTN, **MOE}, i)
                      for i in range(m["n_layers"] - lead)]
        self.embed = _make(key, TOP["embed"], shapes[TOP["embed"]], None)
        self.fn = _make(key, TOP["fn"], shapes[TOP["fn"]], None)
        self.head = _make(key, TOP["head"], shapes[TOP["head"]], None)
        ein = make_ein(prec)
        up = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)
        self._dense = jax.jit(lambda p, h: block(m, ein, up(p), h, True))
        self._moe = jax.jit(lambda p, h: block(m, ein, up(p), h, False))
        self._route = jax.jit(lambda p, h: route(m, ein, up(p), h))

        def read(head, fn, h, picks):
            x = rms(h, fn.astype(jnp.float32), m["norm_eps"])
            z = ein("bsd,dv->bsv", x, head.astype(jnp.float32))
            z = z[..., : m["vocab_size"]]
            picked = jnp.take_along_axis(z, picks[..., None], -1)[..., 0]
            return jnp.max(z, -1), picked, jnp.argmax(z, -1)

        self._read = jax.jit(read)

    def logits_fn(self):
        """The full logits of (B, T) tokens (for small sizes and tests)."""
        m = self.m

        def run(tokens):
            h = self.forward(tokens)
            x = rms(h, self.fn.astype(jnp.float32), m["norm_eps"])
            z = jnp.einsum("bsd,dv->bsv", x, self.head.astype(jnp.float32),
                           precision=HI)
            return z[..., : m["vocab_size"]]

        return run

    def forward(self, tokens):
        h = self.embed[jnp.asarray(tokens)].astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            for p in self.lead:
                h = self._dense(p, h)
            for p in self.units:
                h = self._moe(p, h)
        return h

    def routes(self, tokens) -> list:
        """Each MoE layer's top-k experts at every position of (B, T)
        tokens: a list of (B, T, k) arrays."""
        out = []
        h = self.embed[jnp.asarray(tokens)].astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            for p in self.lead:
                h = self._dense(p, h)
            for p in self.units:
                out.append(np.asarray(self._route(p, h)))
                h = self._moe(p, h)
        return out

    def read(self, tokens: np.ndarray, picks: np.ndarray):
        """Forward over (B, T) token ids. Returns, per position, the best
        logit, the logit of ``picks`` (B, T) and the arg-max token."""
        h = self.forward(tokens)
        with jax.default_matmul_precision("highest"):
            best, picked, top = self._read(self.head, self.fn, h,
                                           jnp.asarray(picks))
        return np.asarray(best), np.asarray(picked), np.asarray(top)
