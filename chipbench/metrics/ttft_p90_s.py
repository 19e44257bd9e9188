"""ttft_p90_s: 90th percentile of time to first token, from each
request's due time, over the requests due in the window."""
from chipbench.window import percentile, ttfts


def read(run, name):
    return percentile(ttfts(run.records, run.window), 90)
