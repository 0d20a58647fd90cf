"""How long a snapshot takes from its clone to its merge at a replica:
95th percentile over the snapshots cloned in the window's cut of the end
of the ``enoki.merge`` that folded its seq minus the start of its
``enoki.replicate``, on the host clock."""
from bench import spanread
from bench.stats import percentile


def read(run):
    recs = spanread.records(run)
    if recs is None:
        return None
    merged = {s: r.end for r in recs if r.name == "enoki.merge"
              for s in r.attrs["seqs"]}
    lags = [merged[s] - r.start for r in recs
            if r.name == "enoki.replicate"
            and r.start < run.t1_ns - spanread.TAIL_NS
            for s in r.attrs["seqs"] if s in merged]
    if not lags:
        return None
    return percentile([x / 1e6 for x in lags], 95)
