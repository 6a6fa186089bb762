"""Device time spent moving the KV cache per engine step (prefill or
decode attempt): the program's ``kv_cache`` scope, and the copies of a
whole stacked cache that XLA places outside it (device trace, the
program's scopes and spans; ``benchlib.program_trace``)."""

from benchlib import traffic

_DTYPE = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}


def cache_shape(r) -> str:
    """The stacked key or value cache: layers, slots, positions, key/value
    heads, head width."""
    m, mix = r["model"], r["mix"]
    s_max = mix["prompt_len"] + traffic.max_new_tokens(mix) + 1
    dims = (m["n_layers"], r["cell"]["slots"], s_max, m["n_kv_heads"],
            m["d_head"])
    return (f"{_DTYPE[m.get('dtype', 'bfloat16')]}"
            f"[{','.join(str(d) for d in dims)}]")


def read(r):
    t = r["trace"] or {}
    c = t.get("program_span_counts", {})
    n = c.get("serve.prefill", 0) + c.get("serve.step", 0)
    s = t.get("scope_s", {}).get("kv_cache", 0.0)
    s += t.get("copy_s", {}).get(cache_shape(r), 0.0)
    if not n or s <= 0:
        return None
    return 1e3 * s / n
