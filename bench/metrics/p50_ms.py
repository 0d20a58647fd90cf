"""Median latency of every request due in the window, from its due
instant to its output on the host; lost requests count as the longest
wait a run allows."""
from bench.stats import percentile


def read(run):
    return percentile(run.latencies_ms(run.attempted()), 50)
