"""83rd percentile of the wait from a request's admission to its wave's
prefill launch, from the runtime's own record (``requests()``: launched
less submitted; host clock)."""

from benchlib.common import nearest_rank


def read(r):
    w = r["counters"].get("admit_wait_s")
    if not w:
        return None
    v = nearest_rank(w, 83)
    return v * 1e3 if v != float("inf") else None
