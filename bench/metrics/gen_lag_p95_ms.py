"""How late the load generator ran: 95th percentile of send instant minus
due instant over the window's requests (host clock)."""
from bench.stats import percentile


def read(run):
    idx = run.attempted()
    h = run.hist
    return percentile((h.send_ns[idx] - h.due_ns[idx]) / 1e6, 95)
