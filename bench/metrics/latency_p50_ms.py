"""Median submit-to-answer time over every request submitted in the
window, on the harness's clock.  A request is what the client submits at
once and waits for, from the submit of its first query to the answer of
its last: one ad-hoc query is one request.  A request with a failed or
shed query counts as missing every limit."""
from yardstick.stats import percentile


def read(rec):
    return 1e3 * percentile(rec["latencies"], 0.50)
