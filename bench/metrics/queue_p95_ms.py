"""How long a request waits before its fold starts: 95th percentile over
requests of the start of the ``enoki.dispatch`` that ran it minus the
start of its ``enoki.request`` (the emulated hop, the window, and any wait
behind a busy pump), on the host clock."""
from bench import spanread
from bench.stats import percentile


def read(run):
    rows = spanread.served(run)
    if rows is None:
        return None
    submit, dispatch, _ = rows
    return percentile((dispatch - submit) / 1e6, 95)
