"""itl_p95_ms: 95th percentile of all gaps between consecutive output
tokens of a request, pooled across requests, whose later token falls in
the window."""
from chipbench.window import percentile, token_gaps


def read(run, name):
    v = percentile(token_gaps(run.records, run.window), 95)
    return None if v is None else 1e3 * v
