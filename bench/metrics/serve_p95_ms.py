"""How long a request takes from the start of the ``enoki.dispatch`` that
ran it to the end of its ``enoki.request`` (fold, device, fetch, timeline
and delivery): 95th percentile over requests, on the host clock."""
from bench import spanread
from bench.stats import percentile


def read(run):
    rows = spanread.served(run)
    if rows is None:
        return None
    _, dispatch, answer = rows
    return percentile((answer - dispatch) / 1e6, 95)
