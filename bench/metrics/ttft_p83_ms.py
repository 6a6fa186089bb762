"""83rd percentile, over every request due in the window, of the time from
when it was due to its first token; a request that failed is missing. The
chat window holds 61 requests: the 83rd is the highest percentile with
ten requests beyond it."""

from benchlib.common import nearest_rank


def read(r):
    v = nearest_rank(r["counters"]["ttft_s"], 83)
    return v * 1e3 if v != float("inf") else None
