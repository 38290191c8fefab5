"""The 99th percentile of request latency, as latency_p50_ms, read in the
traced run.  A window holds about a thousand requests, so this is the
tenth slowest, and the host's stalls of a tenth of a second, which land
in 1 to 11 requests a run, decide whether it reads about 66 ms or 130 ms
and more: too unsteady to bound, so it stands beside the bounded tail,
latency_p95_ms."""
from yardstick.stats import percentile


def read(rec):
    return 1e3 * percentile(rec["latencies"], 0.99)
