"""83rd percentile of the wait from when a request was due to its wave's
prefill launch (host clock, the harness's adapter); the percentile of the
TTFT tail it moves."""

from benchlib.common import nearest_rank


def read(r):
    v = nearest_rank(r["counters"]["wait_s"], 83)
    return v * 1e3 if v != float("inf") else None
