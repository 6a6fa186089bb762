"""Serving driver: batched prefill + decode with resident caches.

Continuous-batching-lite: a request queue is packed into fixed slots; each
engine step decodes one token for every active slot; finished slots are
refilled from the queue (prefill) without stopping the decode stream.

The caches stay resident: each decode step DONATES them, and writes only
position ``pos`` of every position-indexed cache (full K/V, ring K/V, MLA
latent) in place -- no copy of a cache exists at any point of the step
(``models.model.decode_step``). Recurrent states (SSM, RG-LRU) are
O(B * state) and replaced whole; cross-attention caches are only read.

Two paths share the jitted steps:

  Engine.serve        -- the plain happy-path loop (padded last wave uses a
                         MASKED dummy slot, never a duplicated request).
  GuardedEngine + runtime.ServingRuntime -- the resilient path (--guard):
                         bounded admission, per-request deadlines, the
                         census-guarded decode (every step's logit
                         statistic rides the row census ``reduce(...,
                         axis=-1, census=True)`` -- NaN/Inf detected in
                         the SAME launch, per slot, zero extra kernel
                         input bytes), and the
                         per-backend circuit breaker degrading
                         pallas -> mma_jnp -> xla under kernel faults.

  PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --tiny \
      --requests 8 --batch-slots 4 --max-new 16 --guard \
      --chaos --chaos-seed 7 --status-path /tmp/serve_status.json
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import reduce as R
from repro.configs import get_arch
from repro.launch.device import print_device_report, use_compile_cache
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models import init_params, make_caches
from repro.models.model import clear_position, merge_caches, split_caches
from repro.models.frontends import synth_image_embeds
from repro.runtime.metrics import span
from repro.runtime.serving import (
    Request,
    ServingRuntime,
    guarded_logit_stat,
)


def _tok_ints(tok) -> np.ndarray:
    """Per-slot int token from a (B, 1) or (B, 1, K) greedy-argmax output
    (codebook models report codebook 0, as the plain loop always has)."""
    a = np.asarray(tok)
    return a[:, 0] if a.ndim == 2 else a[:, 0, 0]


def _read_back(tok, census):
    """A step's tokens and census on the host: the step's two waits on the
    device."""
    with span("serve.readback"):
        return _tok_ints(tok), np.asarray(census)


class Engine:
    """Greedy decoding engine over fixed batch slots."""

    def __init__(self, cfg, s_max: int, batch_slots: int, seed: int = 0):
        self.cfg = cfg
        self.s_max = s_max
        self.slots = batch_slots
        self.params, _ = init_params(jax.random.PRNGKey(seed), cfg)
        # underscored: GuardedEngine exposes protocol methods named
        # start_wave/decode, which plain attributes here would shadow
        self._jit_prefill = jax.jit(make_prefill_step(cfg, s_max))
        self._jit_decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,))
        self.ctx = (
            synth_image_embeds(
                jax.random.PRNGKey(1), batch_slots, cfg.n_img_tokens,
                cfg.d_model, jnp.dtype(cfg.dtype))
            if cfg.n_img_tokens else None
        )

    def check_fits(self, prompt_len: int, max_new: int) -> None:
        """The cache-overflow guard: a prompt + its generation + the one
        trailing decode position must fit the resident caches."""
        need = int(prompt_len) + int(max_new) + 1
        if need > self.s_max:
            raise ValueError(
                f"prompt_len ({prompt_len}) + max_new ({max_new}) + 1 = "
                f"{need} exceeds the engine's cache length s_max="
                f"{self.s_max}; shorten the request or rebuild the engine"
            )

    def _pack_wave(self, wave: list) -> jnp.ndarray:
        """Stack a wave of prompts into (slots, L), padding the tail with
        MASKED dummy slots (zero prompts, excluded from token accounting by
        the caller) -- never by duplicating a live request."""
        n_live = len(wave)
        if n_live < self.slots:
            dummy = np.zeros_like(np.asarray(wave[0]))
            wave = wave + [dummy] * (self.slots - n_live)
        prompts = jnp.asarray(np.stack(wave))
        if self.cfg.n_codebooks and prompts.ndim == 2:
            prompts = jnp.tile(prompts[..., None], (1, 1, self.cfg.n_codebooks))
        return prompts

    def serve(self, requests: list[np.ndarray], max_new: int) -> list[list[int]]:
        """requests: list of prompt token arrays (same length for packing
        simplicity here; ragged packing is the documented extension).
        An empty request list serves zero requests (no crash)."""
        out: list[list[int]] = []
        if not requests:
            return out
        for r in requests:
            self.check_fits(np.asarray(r).shape[0], max_new)
        queue = list(requests)
        while queue:
            wave = queue[: self.slots]
            queue = queue[self.slots :]
            n_live = len(wave)
            prompts = self._pack_wave(wave)
            caches = make_caches(self.cfg, self.slots, self.s_max)
            logits, caches = self._jit_prefill(self.params, prompts, *(
                (self.ctx,) if self.ctx is not None else ()))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            if tok.ndim == 2:
                tok = tok[:, :1]
            gen = [tok]
            pos = prompts.shape[1]
            for t in range(max_new - 1):
                tok, caches = self._jit_decode(
                    self.params, caches, gen[-1], jnp.asarray(pos + t, jnp.int32),
                    *((self.ctx,) if self.ctx is not None else ()),
                )
                gen.append(tok)
            toks = np.stack([_tok_ints(g) for g in gen], 1)
            out.extend(list(toks[:n_live]))
        return [list(map(int, o)) for o in out]


class GuardedEngine(Engine):
    """``runtime.serving`` protocol over the jitted prefill/decode pair.

    Each step is one jitted function per (stat) backend: model decode +
    the chaos scale multiply (x1.0 = bitwise identity) + the per-slot
    logit statistic with its in-launch non-finite census
    (``guarded_logit_stat`` -- on the kernel backends one pallas_call of
    the segmented gather kernel over the slots' rows of the logits, with
    one compiled body at any slot count and zero input bytes beyond the
    logits the statistic already reads) + the greedy argmax. Keying the
    jitted functions by backend NAME (not the process default) is what
    makes the breaker's re-route safe under jit -- a traced computation
    has its plan baked in, so each backend gets its own trace.

    A decode step donates the wave's position-indexed caches and writes
    position ``pos`` of them in place; ``decode`` moves the written
    buffers into the state it was given, which is the runtime's committed
    state, and into the state it returns. A retry from the committed state
    therefore runs on caches that already hold position ``pos`` from the
    failed attempt. It has the same token and position, and a decode step
    attends no position at or past ``pos`` from the cache; ``decode`` first
    zeroes that position (``models.model.clear_position``, only on a
    retry), since the dots still multiply what they weigh 0 and the failed
    attempt may have written NaN there. So the retry reproduces a clean
    step bitwise without a second copy of the caches. Recurrent
    states are not donated: the committed state keeps the ones the step
    started from, and the step returns new ones. Prefill makes its caches
    inside the step. ``decode_in_place`` counts the decode calls whose
    donated caches were consumed (deleted after the call); a backend that
    declined the donation shows at once as a count below the calls.

    For a MoE configuration, ``moe_held_pairs`` counts the routed (token,
    expert) pairs the decode calls computed on this device's experts, and
    ``moe_decode_calls`` those calls. The step sums its pairs on the device
    and appends the sum to the census it returns anyway; ``decode`` takes
    it off before the runtime sees the census, so the count costs no
    transfer of its own."""

    def __init__(self, cfg, s_max: int, batch_slots: int, seed: int = 0):
        super().__init__(cfg, s_max, batch_slots, seed)
        self._guarded_prefill = {}
        self._guarded_decode = {}
        self._clear = jax.jit(clear_position, donate_argnums=(0,))
        self.decode_in_place = 0
        self.moe_held_pairs = 0
        self.moe_decode_calls = 0

    @property
    def _moe(self) -> bool:
        return self.cfg.moe is not None

    def validate(self, prompt, max_new: int):
        try:
            self.check_fits(np.asarray(prompt).shape[0], max_new)
        except ValueError as e:
            return str(e)
        return None

    def _scale_logits(self, logits, scales):
        s = scales.reshape((-1,) + (1,) * (logits.ndim - 1))
        return logits * s.astype(logits.dtype)

    def _prefill_fn(self, backend):
        fn = self._guarded_prefill.get(backend)
        if fn is not None:
            return fn
        prefill = make_prefill_step(self.cfg, self.s_max)

        def step(params, prompts, scales, ctx=None):
            logits, caches = prefill(params, prompts, ctx)
            logits = self._scale_logits(logits, scales)
            with jax.named_scope("census"):
                stat, census = guarded_logit_stat(logits, backend=backend)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            if tok.ndim == 2:
                tok = tok[:, :1]
            return tok, caches, stat, census

        fn = jax.jit(step)
        self._guarded_prefill[backend] = fn
        return fn

    def _decode_fn(self, backend):
        fn = self._guarded_decode.get(backend)
        if fn is not None:
            return fn
        decode_logits = make_decode_step(self.cfg, greedy=False,
                                         with_stats=self._moe)

        def step(params, indexed, rest, tok, pos, scales, ctx=None):
            logits, caches, *stats = decode_logits(
                params, merge_caches(indexed, rest), tok, pos, ctx)
            logits = self._scale_logits(logits, scales)
            with jax.named_scope("census"):
                stat, census = guarded_logit_stat(logits, backend=backend)
            if self._moe:
                pairs = stats[0]["moe_held_pairs"].astype(census.dtype)
                census = jnp.concatenate([census, pairs[None]])
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            return (nxt,) + split_caches(caches) + (stat, census)

        fn = jax.jit(step, donate_argnums=(1,))
        self._guarded_decode[backend] = fn
        return fn

    # -- the ServingRuntime protocol --------------------------------------

    def start_wave(self, prompts: list, scales, backend: str):
        live = [p for p in prompts if p is not None]
        if not live:
            raise ValueError("start_wave needs at least one live prompt")
        with span("serve.dispatch"):
            packed = self._pack_wave([np.asarray(p) for p in live])
            # dummy-slot scales are 1.0 (the runtime already sends 1.0 for
            # masked slots, but the wave list may be SHORTER than slots)
            s = np.ones((self.slots,), np.float32)
            s[: len(scales)] = np.asarray(scales, np.float32)[: self.slots]
            tok, caches, _stat, census = self._prefill_fn(backend)(
                self.params, packed, jnp.asarray(s),
                *((self.ctx,) if self.ctx is not None else ()),
            )
        state = {"caches": caches, "tok": tok, "pos": int(packed.shape[1]),
                 "t": 0}
        return (state,) + _read_back(tok, census)

    def decode(self, state: dict, scales, backend: str):
        indexed, rest = split_caches(state["caches"])
        with span("serve.dispatch"):
            pos = jnp.asarray(state["pos"] + state["t"], jnp.int32)
            if state.get("attempted"):
                indexed = self._clear(indexed, pos)
            s = np.ones((self.slots,), np.float32)
            s[: len(scales)] = np.asarray(scales, np.float32)[: self.slots]
            tok, indexed_new, rest_new, _stat, census = self._decode_fn(backend)(
                self.params, indexed, rest, state["tok"], pos, jnp.asarray(s),
                *((self.ctx,) if self.ctx is not None else ()),
            )
        self.decode_in_place += all(
            x.is_deleted() for x in jax.tree.leaves(indexed))
        # the donated buffers are gone: the committed state now holds the
        # written ones (see the class docstring for why a retry stays exact)
        state["caches"] = merge_caches(indexed_new, rest)
        state["attempted"] = True
        new_state = {"caches": merge_caches(indexed_new, rest_new), "tok": tok,
                     "pos": state["pos"], "t": state["t"] + 1}
        tok, census = _read_back(tok, census)
        if self._moe:
            self.moe_held_pairs += int(census[-1])
            self.moe_decode_calls += 1
            census = census[:-1]
        return new_state, tok, census


def main(argv=None):
    """Serve from the command line. Returns the generated token lists, or
    under ``--guard`` the runtime's per-request results and its metrics
    snapshot."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument(
        "--reduce-backend",
        default=None,
        choices=R.available_backends() + ("auto",),
        help="process-wide repro.reduce backend (default: cost-model auto)",
    )
    ap.add_argument("--guard", action="store_true",
                    help="serve through the resilient runtime (admission "
                    "queue, deadlines, census-guarded decode, breaker)")
    ap.add_argument("--queue-capacity", type=int, default=64)
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline, seconds from submission")
    ap.add_argument("--chaos", action="store_true",
                    help="per-request fault injection (--guard only)")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--status-path", default=None,
                    help="atomic JSON ServeMetrics export path")
    args = ap.parse_args(argv)

    use_compile_cache()
    print_device_report()
    if args.reduce_backend:
        R.set_default_backend(args.reduce_backend)
    cfg = get_arch(args.arch, tiny=args.tiny)
    s_max = args.prompt_len + args.max_new + 1
    rng = np.random.default_rng(0)
    reqs = [
        rng.integers(0, cfg.vocab_size, size=(args.prompt_len,)).astype(np.int32)
        for _ in range(args.requests)
    ]
    t0 = time.time()
    if args.guard:
        from repro.runtime.chaos import ChaosMonkey

        eng = GuardedEngine(cfg, s_max, args.batch_slots)
        chaos = (
            ChaosMonkey.from_seed(
                args.chaos_seed, n_steps=args.requests,
                nan_rate=0.15, fail_rate=0.15, preempt_rate=0.1,
            )
            if args.chaos else None
        )
        runtime = ServingRuntime(
            eng, queue_capacity=args.queue_capacity, chaos=chaos,
            status_path=args.status_path,
        )
        now = runtime.clock()
        results = runtime.serve([
            Request(
                rid=i, prompt=p, max_new=args.max_new,
                deadline_s=(now + args.deadline_s
                            if args.deadline_s is not None else None),
            )
            for i, p in enumerate(reqs)
        ])
        dt = time.time() - t0
        outs = [list(r.tokens) for r in results if r.ok]
        n_tok = sum(len(o) for o in outs)
        snap = runtime.metrics.snapshot()
        print(f"served {len(outs)}/{len(reqs)} requests, {n_tok} tokens in "
              f"{dt:.2f}s ({n_tok / max(dt, 1e-9):.1f} tok/s incl. compile)")
        print(f"admitted={snap['admitted']} shed={snap['shed_queue_full']}"
              f"+{snap['shed_infeasible']} deadline_missed="
              f"{snap['deadline_missed']} quarantined={snap['quarantined']} "
              f"breaker_trips={snap['breaker_trips']}")
        print(f"ttft p50={snap['ttft_p50_s'] * 1e3:.1f}ms "
              f"p99={snap['ttft_p99_s'] * 1e3:.1f}ms  "
              f"itl p50={snap['itl_p50_s'] * 1e3:.1f}ms "
              f"p99={snap['itl_p99_s'] * 1e3:.1f}ms  "
              f"live slots {snap['live_slot_steps']}/{snap['slot_steps']}  "
              f"decode in place {snap['decode_in_place']}")
        if cfg.moe is not None:
            print(f"moe routed pairs on held experts {snap['moe_held_pairs']}"
                  f" in {snap['moe_decode_calls']} decode calls")
        return results, snap
    eng = Engine(cfg, s_max, args.batch_slots)
    outs = eng.serve(reqs, args.max_new)
    dt = time.time() - t0
    n_tok = sum(len(o) for o in outs)
    print(f"served {len(outs)} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok/dt:.1f} tok/s incl. compile)")
    for i, o in enumerate(outs[:3]):
        print(f"req{i}: {o[:12]}...")
    return outs


if __name__ == "__main__":
    main()
