"""Tokens of the steps taken in the window, over the window (host clock)."""


def read(r):
    c = r["counters"]
    return c["tokens"] / c["window_s"]
