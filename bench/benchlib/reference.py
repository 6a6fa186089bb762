"""The plain reference of a dense decoder (OLMo, InternLM2 and their kind),
written from the published architecture and sharing no code with the
program: embedding, pre-norm blocks of causal self-attention with rotary
positions (rotate-half) and grouped key/value heads, a SwiGLU feed-forward,
a final norm and the output head; cross-entropy over the vocabulary; and
AdamW with global-norm clipping as the configuration's training settings
state.

Everything is float32 with every matrix product at ``HIGHEST`` precision.
``prec="fp8"`` is the control: the same arithmetic with the operands of
every matrix product rounded to float8 (e4m3, one scale per tensor), the
nearest precision below the configurations' bfloat16.

The reference runs layer by layer so that it fits the chip beside nothing
else: the training step keeps one activation per layer and takes each
layer's gradient by its own vector-Jacobian product, twice -- once for the
global gradient norm that clipping needs, once to update the layer.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import weights

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0

# the program's parameter paths, by the reference's short names
LAYER = {
    "n1": "['units']['pos0']['norm1']['scale']",
    "q": "['units']['pos0']['mix']['q']['w']",
    "k": "['units']['pos0']['mix']['k']['w']",
    "v": "['units']['pos0']['mix']['v']['w']",
    "o": "['units']['pos0']['mix']['o']['w']",
    "n2": "['units']['pos0']['norm2']['scale']",
    "gate": "['units']['pos0']['ffn']['gate']['w']",
    "up": "['units']['pos0']['ffn']['up']['w']",
    "down": "['units']['pos0']['ffn']['down']['w']",
}
TOP = {
    "embed": "['embed']['table']",
    "fn": "['final_norm']['scale']",
    "head": "['head']['w']",
}


def _q8(x):
    """Round to float8 e4m3 with one scale per tensor; the gradient passes
    straight through."""
    s = jnp.max(jnp.abs(x)) / F8_MAX + 1e-30
    y = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(y - x)


def make_ein(prec: str):
    if prec == "f32":
        return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HI)
    if prec == "fp8":
        return lambda spec, a, b: jnp.einsum(spec, _q8(a), _q8(b),
                                             precision=HI)
    raise ValueError(prec)


def norm(m: dict, scale, x):
    eps = m["norm_eps"]
    if m["norm"] == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * scale
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def rope(x, theta: float):
    """x: (B, S, H, D); rotate-half over the full head at positions 0..S-1."""
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(m: dict, ein, p: dict, h):
    """One decoder layer on h: (B, S, d) float32."""
    b, s, _ = h.shape
    nh, nkv, dh = m["n_heads"], m["n_kv_heads"], m["d_head"]
    x = norm(m, p.get("n1"), h)
    q = ein("bsd,dk->bsk", x, p["q"]).reshape(b, s, nh, dh)
    k = ein("bsd,dk->bsk", x, p["k"]).reshape(b, s, nkv, dh)
    v = ein("bsd,dk->bsk", x, p["v"]).reshape(b, s, nkv, dh)
    q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    sc = ein("bqhd,bkhd->bhqk", q, k) * dh ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal, sc, -jnp.inf)
    att = jax.nn.softmax(sc, axis=-1)
    o = ein("bhqk,bkhd->bqhd", att, v).reshape(b, s, nh * dh)
    h = h + ein("bsk,kd->bsd", o, p["o"])
    x = norm(m, p.get("n2"), h)
    f = jax.nn.silu(ein("bsd,df->bsf", x, p["gate"])) * ein(
        "bsd,df->bsf", x, p["up"])
    return h + ein("bsf,fd->bsd", f, p["down"])


def logits(m: dict, ein, head, fn, h):
    """Final norm and head; the columns of the vocabulary only."""
    x = norm(m, fn, h)
    if m["tie_embeddings"]:
        z = ein("bsd,vd->bsv", x, head)
    else:
        z = ein("bsd,dv->bsv", x, head)
    return z[..., : m["vocab_size"]]


def head_loss(m: dict, ein, head, fn, h, labels):
    z = logits(m, ein, head, fn, h)
    lse = jax.nn.logsumexp(z, -1)
    picked = jnp.take_along_axis(z, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)


# The program holds the vocabulary's rows padded to a multiple of this;
# the pad rows are never looked up and their logits are masked.
VOCAB_ROWS = 256


def leaf_shapes(m: dict) -> dict:
    """``{path: ShapeDtypeStruct}`` of the weights in the program's layout,
    from the configuration alone: stacked layers, padded vocabulary rows."""
    d, nl, ff = m["d_model"], m["n_layers"], m["d_ff"]
    hd, kvd = m["n_heads"] * m["d_head"], m["n_kv_heads"] * m["d_head"]
    rows = -(-m["vocab_size"] // VOCAB_ROWS) * VOCAB_ROWS
    shapes = {LAYER["q"]: (nl, d, hd), LAYER["k"]: (nl, d, kvd),
              LAYER["v"]: (nl, d, kvd), LAYER["o"]: (nl, hd, d),
              LAYER["gate"]: (nl, d, ff), LAYER["up"]: (nl, d, ff),
              LAYER["down"]: (nl, ff, d), TOP["embed"]: (rows, d)}
    if m["norm"] == "rmsnorm":
        shapes.update({LAYER["n1"]: (nl, d), LAYER["n2"]: (nl, d),
                       TOP["fn"]: (d,)})
    if not m["tie_embeddings"]:
        shapes[TOP["head"]] = (d, rows)
    dt = jnp.dtype(m["dtype"])
    return {k: jax.ShapeDtypeStruct(v, dt) for k, v in shapes.items()}


class Weights:
    """The benchmark's weights, regenerated from the seed one layer at a
    time, as the reference holds them."""

    def __init__(self, m: dict, key):
        self.key = key
        self.shapes = leaf_shapes(m)

    def layer(self, i: int, dtype=jnp.float32) -> dict:
        out = {}
        for name, path in LAYER.items():
            if path in self.shapes:
                s = self.shapes[path]
                out[name] = self._make(path, s, i).astype(dtype)
        return out

    def top(self, name: str, dtype=jnp.float32):
        path = TOP[name]
        if path not in self.shapes:
            return None
        return self._make(path, self.shapes[path], None).astype(dtype)

    def _make(self, path, s, layer):
        key = (path, s.shape, str(s.dtype), layer is None)
        if key not in _MAKERS:
            _MAKERS[key] = jax.jit(
                lambda k, i: weights.layer_of(k, path, s.shape, s.dtype, i),
                static_argnums=() if layer is not None else (1,))
        return _MAKERS[key](self.key, layer)


_MAKERS: dict = {}


def cosine_lr(t: dict, step: int) -> float:
    """The configuration's schedule: linear warm-up, cosine decay."""
    warm = min(step / max(t["warmup_steps"], 1), 1.0)
    prog = min(max((step - t["warmup_steps"])
                   / max(t["total_steps"] - t["warmup_steps"], 1), 0.0), 1.0)
    return t["learning_rate"] * warm * 0.5 * (1 + math.cos(math.pi * prog))


class TrainReference:
    """AdamW training of the plain model from the benchmark's weights.

    ``step(tokens)`` takes one (B, S + 1) batch and returns the loss;
    ``grad_norms`` holds each step's global gradient norm; after the first
    step ``first_grad`` holds the clipped gradient's norm per leaf and
    layer, and ``change()`` the norm of each leaf's change since the
    start."""

    def __init__(self, cfg: dict, key, train: dict, prec="f32"):
        self.m = cfg
        self.t = train
        self.w = Weights(cfg, key)
        ein = make_ein(prec)
        m = cfg
        self.tied = m["tie_embeddings"]
        self.L = m["n_layers"]
        self.layers = [self.w.layer(i) for i in range(self.L)]
        self.embed = self.w.top("embed")
        self.fn = self.w.top("fn")
        self.head = self.embed if self.tied else self.w.top("head")
        self.mom: dict = {}
        self.n = 0
        self.grad_norms: list = []
        self.first_grad: dict = {}

        self._fwd = jax.jit(lambda p, h: block(m, ein, p, h))

        def bvjp(p, h, dh):
            _, f = jax.vjp(lambda pp, hh: block(m, ein, pp, hh), p, h)
            return f(dh)

        self._bvjp = jax.jit(bvjp)

        def hvjp(head, fn, h, labels):
            loss, f = jax.vjp(
                lambda a, b, c: head_loss(m, ein, a, b, c, labels),
                head, fn, h)
            return (loss,) + f(jnp.ones((), jnp.float32))

        self._hvjp = jax.jit(hvjp)
        self._sumsq = jax.jit(
            lambda t: sum(jnp.sum(x * x) for x in jax.tree.leaves(t)))
        self._embed_grad = jax.jit(
            lambda tok, dh, vp: jnp.zeros((vp, dh.shape[-1]), jnp.float32)
            .at[tok.reshape(-1)].add(dh.reshape(-1, dh.shape[-1])),
            static_argnums=2)
        b1, b2, wd = train["b1"], train["b2"], train["weight_decay"]

        def adam(p, g, mo, v, clip, lr, bc1, bc2):
            gf = g * clip
            mo = b1 * mo + (1 - b1) * gf
            v = b2 * v + (1 - b2) * gf * gf
            delta = (mo / bc1) / (jnp.sqrt(v / bc2) + 1e-8) + wd * p
            return p - lr * delta, mo, v

        self._adam = jax.jit(adam, donate_argnums=(0, 2, 3))

    def _update(self, name, p, g, clip, lr, bc1, bc2):
        if name not in self.mom:
            self.mom[name] = (jnp.zeros_like(p), jnp.zeros_like(p))
        mo, v = self.mom.pop(name)
        p, mo, v = self._adam(p, g, mo, v, clip, lr, bc1, bc2)
        self.mom[name] = (mo, v)
        return p

    def step(self, tokens: np.ndarray) -> float:
        tok = jnp.asarray(tokens[:, :-1])
        labels = jnp.asarray(tokens[:, 1:])
        hs = [self.embed[tok]]
        for p in self.layers:
            hs.append(self._fwd(p, hs[-1]))
        loss, d_head, d_fn, dh_top = self._hvjp(self.head, self.fn, hs[-1],
                                                labels)
        # pass 1: the global norm of the gradient
        ss = 0.0 if self.fn is None else float(self._sumsq(d_fn))
        dh = dh_top
        for i in reversed(range(self.L)):
            dp, dh = self._bvjp(self.layers[i], hs[i], dh)
            ss += float(self._sumsq(dp))
        d_embed = self._embed_grad(tok, dh, self.embed.shape[0])
        if self.tied:
            d_embed = d_embed + d_head
        else:
            ss += float(self._sumsq(d_head))
        ss += float(self._sumsq(d_embed))
        gnorm = math.sqrt(ss)
        self.grad_norms.append(gnorm)
        clip = min(1.0, self.t["grad_clip"] / max(gnorm, 1e-9))
        # pass 2: the update, one layer at a time
        self.n += 1
        lr = cosine_lr(self.t, self.n)
        bc1 = 1 - self.t["b1"] ** self.n
        bc2 = 1 - self.t["b2"] ** self.n
        args = (jnp.float32(clip), jnp.float32(lr), jnp.float32(bc1),
                jnp.float32(bc2))
        first = self.n == 1
        dh = dh_top
        for i in reversed(range(self.L)):
            dp, dh = self._bvjp(self.layers[i], hs[i], dh)
            hs[i + 1] = None
            new = {}
            for name, g in dp.items():
                if first:
                    self.first_grad[f"{LAYER[name]}[{i}]"] = clip * float(
                        jnp.sqrt(self._sumsq(g)))
                new[name] = self._update(f"{name}{i}", self.layers[i][name],
                                         g, *args)
            self.layers[i] = new
        del hs
        d_embed = self._embed_grad(tok, dh, self.embed.shape[0])
        if self.tied:
            d_embed = d_embed + d_head
        tops = [("embed", d_embed)]
        if self.fn is not None:
            tops.append(("fn", d_fn))
        if not self.tied:
            tops.append(("head", d_head))
        for name, g in tops:
            if first:
                self.first_grad[TOP[name]] = clip * float(
                    jnp.sqrt(self._sumsq(g)))
            cur = {"embed": self.embed, "fn": self.fn,
                   "head": self.head}[name]
            new = self._update(name, cur, g, *args)
            if name == "embed":
                self.embed = new
                if self.tied:
                    self.head = new
            elif name == "fn":
                self.fn = new
            else:
                self.head = new
        return float(loss)

    def change(self) -> dict:
        """Norm of each leaf's (and layer's) change since the start."""
        out = {}
        diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum((a - b) ** 2)))
        for i in range(self.L):
            p0 = self.w.layer(i)
            for name, p in self.layers[i].items():
                out[f"{LAYER[name]}[{i}]"] = float(diff(p, p0[name]))
        for name in ("embed", "fn", "head"):
            cur = {"embed": self.embed, "fn": self.fn,
                   "head": None if self.tied else self.head}[name]
            if cur is not None:
                out[TOP[name]] = float(diff(cur, self.w.top(name)))
        return out


class ServeReference:
    """Teacher-forced logits of the plain model over whole sequences, in
    blocks of rows, with the weights held in their served bfloat16 (exact
    values) and widened to float32 inside each layer."""

    def __init__(self, cfg: dict, key, prec="f32"):
        m = cfg
        self.m = m
        w = Weights(cfg, key)
        self.layers = [w.layer(i, jnp.bfloat16) for i in range(m["n_layers"])]
        self.fn = w.top("fn", jnp.bfloat16)
        self.head = w.top("embed" if m["tie_embeddings"] else "head",
                          jnp.bfloat16)
        self.embed = w.top("embed", jnp.bfloat16)
        ein = make_ein(prec)
        up = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)
        self._fwd = jax.jit(lambda p, h: block(m, ein, up(p), h))

        def read(head, fn, h, picks):
            z = logits(m, ein, up(head), None if fn is None else up(fn), h)
            picked = jnp.take_along_axis(z, picks[..., None], -1)[..., 0]
            return jnp.max(z, -1), picked, jnp.argmax(z, -1)

        self._read = jax.jit(read)

    def read(self, tokens: np.ndarray, picks: np.ndarray):
        """Forward over (B, T) token ids. Returns, per position, the best
        logit, the logit of ``picks`` (B, T) and the arg-max token."""
        h = self.embed[jnp.asarray(tokens)].astype(jnp.float32)
        for p in self.layers:
            h = self._fwd(p, h)
        best, picked, top = self._read(self.head, self.fn, h,
                                       jnp.asarray(picks))
        return np.asarray(best), np.asarray(picked), np.asarray(top)
