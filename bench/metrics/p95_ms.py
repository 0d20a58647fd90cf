"""95th percentile of the latency of every request due in the window
(due instant to output on the host; lost requests at the longest wait)."""
from bench.stats import percentile


def read(run):
    return percentile(run.latencies_ms(run.attempted()), 95)
