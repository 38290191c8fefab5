"""The 95th percentile of request latency, as latency_p50_ms."""
from yardstick.stats import percentile


def read(rec):
    return 1e3 * percentile(rec["latencies"], 0.95)
