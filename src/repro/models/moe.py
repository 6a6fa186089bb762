"""Mixture-of-Experts FFN: top-k routing, a dropless dispatch for serving
and a sort-based capacity dispatch for training.

Serving (``moe_apply_dropless``, prefill and decode) drops no token: the
routed (token, expert) pairs are sorted by expert, the held experts' pairs
form contiguous groups that grouped matmuls (``jax.lax.ragged_dot``) run
over, and each token's pairs are combined in a fixed order (slot 0 to
k - 1). A request's output therefore never depends on what the other
requests of its wave route to. Training (``moe_apply``) keeps the
per-sequence capacity dispatch below: its gradients flow through fixed
(E, C) blocks, and the load-balance loss keeps drops rare.

Both route over all ``n_experts`` router outputs. The dropless dispatch
computes only the experts this device holds (``MoEConfig.held_start`` /
``n_held``); a pair routed to an expert held elsewhere adds nothing here.
The capacity dispatch requires every expert held. Gates are the
softmax's top-k, renormalised when ``norm_topk_prob``, else scaled by
``routed_scaling_factor``. Shared experts, when configured, act on every
token as one SwiGLU. Scopes: ``moe_router`` (router, softmax, top-k, the
sort), ``moe_experts`` (the routed experts and their combine),
``moe_shared``.

The capacity dispatch itself is reduction-as-matmul in the paper's spirit:
tokens are gathered per-expert into a dense (E, C, d) block so the expert
FFNs run as batched MXU einsums, and the combine is a gate-weighted segment
reduction.
Router softmax and the load-balance statistics (per-expert token fractions,
mean gate mass -- arithmetic reductions over all tokens) ride the MMA path.

Expert-parallel sharding: the leading E axis of every expert weight carries
the "experts" logical axis -> mesh "model" axis (EP).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import reduce as R
from repro.models import context as CTX
from repro.models import layers as L
from repro.models import params as P


def _data_degree() -> int:
    sh = CTX.get_activation_sharding()
    if sh is None:
        return 1
    spec0 = sh.spec[0] if len(sh.spec) else None
    if spec0 is None:
        return 1
    axes = spec0 if isinstance(spec0, tuple) else (spec0,)
    deg = 1
    for ax in axes:
        deg *= sh.mesh.shape[ax]
    return deg


def _model_degree() -> int:
    sh = CTX.get_activation_sharding()
    if sh is None or "model" not in sh.mesh.shape:
        return 1
    return sh.mesh.shape["model"]


# Perf-loop switch: explicit shard_map dispatch/combine vs GSPMD-constrained.
# MEASURED (EXPERIMENTS.md Perf iteration 2): shard_map = 5738 MB static loop
# wire vs 5537 MB constrained on dbrx train_4k -- hypothesis REFUTED (GSPMD's
# boundary reshards around the manual region offset the dispatch savings), so
# the constrained path is the default; the switch stays for future meshes.
USE_SHARD_MAP_DISPATCH = False


def moe_init(key, cfg):
    e = cfg.moe
    d = cfg.d_model
    ks = P.split(key, 5)
    dt = jnp.dtype(cfg.dtype)

    def expert_w(key, din, dout):
        return (
            jax.random.normal(key, (e.n_held, din, dout), jnp.float32) * din**-0.5
        ).astype(dt)

    params = {
        "router": (jax.random.normal(ks[0], (d, e.n_experts), jnp.float32) * d**-0.5
                   ).astype(jnp.float32),  # router stays f32 (routing stability)
        "gate": expert_w(ks[1], d, e.d_ff_expert),
        "up": expert_w(ks[2], d, e.d_ff_expert),
        "down": expert_w(ks[3], e.d_ff_expert, d),
    }
    axes = {
        "router": ("embed", None),
        "gate": ("experts", "embed", "ffn"),
        "up": ("experts", "embed", "ffn"),
        "down": ("experts", "ffn", "embed"),
    }
    if cfg.ffn_kind != "swiglu":
        params.pop("gate")
        axes.pop("gate")
    if e.n_shared_experts:
        params["shared"], axes["shared"] = L.ffn_init(
            ks[4], d, e.n_shared_experts * e.d_ff_expert, cfg.ffn_kind, dt)
    return params, axes


@jax.named_scope("moe_router")
def route(p, x, cfg):
    """x: (..., d) -> (logits and probs (..., E), gates and experts
    (..., k)). The router runs in f32 at full precision: a near tie
    rounded to bf16 would pick another expert."""
    e = cfg.moe
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    probs = L.softmax_mma(logits, mma=cfg.mma_reductions)
    gate_vals, expert_ix = jax.lax.top_k(probs, e.top_k)
    if e.norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, -1, keepdims=True), 1e-9
        )
    else:
        gate_vals = gate_vals * e.routed_scaling_factor
    return logits, probs, gate_vals, expert_ix


@jax.named_scope("moe_shared")
def _shared(p, x, cfg):
    return L.ffn_apply(p["shared"], x, cfg.ffn_kind)


def held_mask(expert_ix, cfg):
    """Which routed pairs this device computes: their expert is held here."""
    e = cfg.moe
    local = expert_ix - e.held_start
    return (local >= 0) & (local < e.n_held)


# Tokens per grouped-matmul pass of the dropless dispatch: a prefill's
# tokens go through in chunks, so its sorted pair rows (chunk * k of them)
# stay small beside the caches.
DROPLESS_CHUNK = 8192


def _experts_dropless(p, x, gates, expert_ix, cfg):
    """The held experts' part of the routed output for x: (T, d) tokens with
    gates and experts (T, k). Returns (y (T, d), routed pairs on held
    experts)."""
    e = cfg.moe
    t, k = expert_ix.shape
    held = held_mask(expert_ix, cfg)
    with jax.named_scope("moe_router"):
        # held experts' pairs first, grouped by expert; the rest last
        key = jnp.where(held, expert_ix - e.held_start, e.n_held).reshape(-1)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((e.n_held + 1,), jnp.int32).at[key].add(1)[:-1]
    with jax.named_scope("moe_experts"):
        rows = x[order // k]                                   # (T*k, d)
        dt = x.dtype

        def gmm(a, w):
            return jax.lax.ragged_dot(a, w.astype(dt), sizes,
                                      preferred_element_type=jnp.float32)

        if cfg.ffn_kind == "swiglu":
            h = jax.nn.silu(gmm(rows, p["gate"])) * gmm(rows, p["up"])
        else:
            h = jax.nn.gelu(gmm(rows, p["up"]))
        out = gmm(h.astype(dt), p["down"])                     # (T*k, d)
        # back to (token, slot) order; rows past the held groups are unset
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.size, dtype=order.dtype))
        pairs = out[inv].reshape(t, k, -1)
        y = jnp.zeros((t, x.shape[-1]), jnp.float32)
        for j in range(k):                                     # fixed order
            y = y + jnp.where(held[:, j, None],
                              pairs[:, j] * gates[:, j, None], 0.0)
    return y.astype(dt), jnp.sum(held, dtype=jnp.int32)


def moe_apply_dropless(p, x, cfg):
    """x: (B, S, d) -> (y, {"moe_held_pairs": routed pairs computed here}).

    Every routed pair is computed (no capacity), on this device's held
    experts; tokens go through ``DROPLESS_CHUNK`` at a time."""
    e = cfg.moe
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    _, _, gates, experts = route(p, xt, cfg)
    n = b * s
    if n <= DROPLESS_CHUNK:
        y, pairs = _experts_dropless(p, xt, gates, experts, cfg)
    else:
        c = -(-n // DROPLESS_CHUNK)
        pad = c * DROPLESS_CHUNK - n

        def chunked(a, fill):
            a = jnp.concatenate(
                [a, jnp.full((pad,) + a.shape[1:], fill, a.dtype)], 0)
            return a.reshape((c, DROPLESS_CHUNK) + a.shape[1:])

        # padding tokens route to no held expert: they add nothing
        ys, counts = jax.lax.map(
            lambda a: _experts_dropless(p, *a, cfg),
            (chunked(xt, 0), chunked(gates, 0), chunked(experts, -1)))
        y, pairs = ys.reshape(-1, d)[:n], jnp.sum(counts)
    y = y.reshape(b, s, d)
    if e.n_shared_experts:
        y = y + _shared(p, x, cfg)
    return y, {"moe_held_pairs": pairs}


def _dispatch_row(expert_ix, gate_vals, n_experts: int, cap: int, backend=None):
    """Per-group dispatch: (S, k) routed pairs -> (E, C) slot tables.

    Runs entirely within one routing group (one sequence), so under GSPMD it
    never crosses the data axis -- this is GShard's group-wise routing, and
    it is what keeps MoE dispatch local (global-argsort dispatch replicates
    the token tensor across the mesh; caught by the dry-run, see DESIGN.md).
    """
    s, k = expert_ix.shape
    flat_expert = expert_ix.reshape(-1)                      # (S*k,)
    flat_token = jnp.repeat(jnp.arange(s), k)
    flat_gate = gate_vals.reshape(-1)
    order = jnp.argsort(flat_expert, stable=True)
    se, st, sg = flat_expert[order], flat_token[order], flat_gate[order]
    # Expert slot bases via the engine scan: the EXCLUSIVE prefix of the
    # per-expert routed counts equals searchsorted(se, arange(E)) on the
    # sorted keys, and counts < 2^24 make the f32 prefix integer-exact.
    counts = jax.ops.segment_sum(
        jnp.ones_like(se, jnp.float32), se, num_segments=n_experts
    )
    start = R.scan(counts, inclusive=False, backend=backend).astype(jnp.int32)
    within = jnp.arange(se.size) - start[se]
    keep = within < cap
    slot = jnp.where(keep, se * cap + within, n_experts * cap)  # overflow slot
    slot_token = jnp.full((n_experts * cap + 1,), s, jnp.int32)
    slot_token = slot_token.at[slot].set(jnp.where(keep, st, s).astype(jnp.int32))
    slot_gate = jnp.zeros((n_experts * cap + 1,), jnp.float32)
    slot_gate = slot_gate.at[slot].set(jnp.where(keep, sg, 0.0))
    return (
        slot_token[:-1].reshape(n_experts, cap),
        slot_gate[:-1].reshape(n_experts, cap),
        keep,
    )


def moe_apply(p, x, cfg):
    """x: (B, S, d) -> (y, aux_metrics).

    Group-wise top-k routing (groups = sequences): each batch row routes its
    S tokens into (E, C_row) capacity slots locally; expert FFNs run as
    (B, E, C, d) einsums sharded batch->data, experts->model (EP). Capacity-
    dropped tokens pass through the residual unchanged."""
    e = cfg.moe
    assert e.holds_all, "a held share of the experts is served, not trained"
    b, s, d = x.shape
    logits, probs, gate_vals, expert_ix = route(p, x, cfg)  # (B,S,E), (B,S,k)
    cap = int(max(1, round(s * e.top_k / e.n_experts * e.capacity_factor)))

    # Dispatch offsets route through the engine scan. The site is vmapped,
    # so Pallas/segmented backends degrade to the mma_jnp einsum route
    # (identical f32-exact integer prefixes, no pallas_call under vmap).
    _rb = R.backend_for_flags(cfg.mma_reductions)
    _sb = _rb if _rb in ("xla", "mma_jnp") else "mma_jnp"
    slot_token, slot_gate, keep = jax.vmap(
        lambda ei, gv: _dispatch_row(ei, gv, e.n_experts, cap, backend=_sb)
    )(expert_ix, gate_vals)                                   # (B,E,C) x2, (B,S*k)

    xpad = jnp.concatenate([x, jnp.zeros((b, 1, d), x.dtype)], 1)  # (B,S+1,d)
    # Dispatch gather runs in an explicitly-local shard_map region: batch
    # rows stay on their data shard, and each model rank gathers only ITS
    # experts' slots (slot tables sharded over the model axis). GSPMD's
    # gather partitioner otherwise replicates the activations in f32
    # ("involuntary full rematerialization"; Perf iteration 2).
    from jax.sharding import PartitionSpec as P

    bsp = CTX.batch_axis_entry()
    use_sm = (
        USE_SHARD_MAP_DISPATCH
        and bsp is not None
        and b % max(1, _data_degree()) == 0
        and e.n_experts % _model_degree() == 0
    )
    if use_sm:
        gfn = CTX.shard_map_specs(
            jax.vmap(lambda xr, ix: xr[ix]),
            in_specs=(P(bsp, None, None), P(bsp, "model", None)),
            out_specs=P(bsp, "model", None, None),
        )
        gathered = gfn(xpad, slot_token)                           # (B,E,C,d)
    else:
        gathered = jax.vmap(lambda xr, ix: xr[ix])(xpad, slot_token)
        gathered = CTX.constrain_moe_dispatch(gathered)

    # ---- expert FFNs as batched einsums (MXU; E sharded over model) ----
    if cfg.ffn_kind == "swiglu":
        h = jax.nn.silu(
            jnp.einsum("becd,edf->becf", gathered, p["gate"].astype(x.dtype))
        ) * jnp.einsum("becd,edf->becf", gathered, p["up"].astype(x.dtype))
    else:
        h = jax.nn.gelu(
            jnp.einsum("becd,edf->becf", gathered, p["up"].astype(x.dtype))
        )
    yexp = CTX.constrain_moe_dispatch(
        jnp.einsum("becf,efd->becd", h, p["down"].astype(x.dtype))
    )  # (B,E,C,d)

    # ---- gate-weighted combine back to tokens ----
    # Local per-expert-shard segment-sum, then ONE explicit psum over the
    # model axis of the token-space partials: the EP combine moves S*d
    # activations once instead of GSPMD's E*C*d f32 reshard (Perf iter. 2).
    # slot_gate is cast to the activation dtype BEFORE the multiply: an f32
    # gate here promotes the whole combine -- and via its cotangents every
    # FSDP weight gather in the backward pass -- to f32, doubling wire bytes
    # (Perf iteration 2b).
    yflat = (yexp * slot_gate[..., None].astype(yexp.dtype)).reshape(b, -1, d)
    seg = lambda yr, ix: jax.ops.segment_sum(yr, ix, num_segments=s + 1)
    if use_sm:
        def combine(yfl, ix):
            partial = jax.vmap(seg)(yfl, ix)       # (B_loc, S+1, d) this shard
            return jax.lax.psum(partial, "model")

        sfn = CTX.shard_map_specs(
            combine,
            in_specs=(P(bsp, "model", None), P(bsp, "model")),
            out_specs=P(bsp, None, None),
        )
        y = sfn(yflat, slot_token.reshape(b, -1))[:, :s]
    else:
        y = jax.vmap(seg)(yflat, slot_token.reshape(b, -1))[:, :s]

    # ---- aux losses: reductions over all tokens (MMA path) ----
    # Both load-balance statistics (per-expert token fractions f_e and mean
    # gate mass P_e) are per-expert reductions over all B*S tokens; instead
    # of two separate launches they batch into ONE reduce_many row pass
    # (each statistic contributes E rows of B*S token values).
    ones_k = jax.nn.one_hot(expert_ix, e.n_experts, dtype=jnp.float32)  # (B,S,k,E)
    t = b * s
    counts = ones_k.sum(2)                                              # (B,S,E)
    tpe_sum, prob_sum = R.reduce_many(
        [jnp.moveaxis(counts, -1, 0).reshape(e.n_experts, -1),
         jnp.moveaxis(probs, -1, 0).reshape(e.n_experts, -1)],
        axis=-1,
        backend=_rb,
    )
    tokens_per_expert = tpe_sum / t                                     # f_e
    mean_prob = prob_sum / t                                            # P_e
    aux = e.n_experts * jnp.sum(tokens_per_expert * mean_prob)
    zloss = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
    metrics = {
        "moe_aux": aux * e.aux_loss_weight,
        "moe_z": zloss * e.router_z_weight,
        "moe_drop_frac": 1.0 - jnp.sum(keep) / keep.size,
    }
    y = y.astype(x.dtype)
    if e.n_shared_experts:
        y = y + _shared(p, x, cfg)
    return y, metrics
