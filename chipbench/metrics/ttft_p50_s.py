"""ttft_p50_s: median time to first token, from each request's due time,
over the requests due in the window."""
from chipbench.window import percentile, ttfts


def read(run, name):
    return percentile(ttfts(run.records, run.window), 50)
