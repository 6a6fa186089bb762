"""95th percentile of the gaps between consecutive output tokens of one
request, over all gaps in the window."""

from benchlib.common import nearest_rank


def read(r):
    gaps = r["counters"]["itl_s"]
    return nearest_rank(gaps, 95) * 1e3 if gaps else None
