"""Share of the fold's scan steps that are bucket padding: the sum over
the window's ``enoki.dispatch`` spans of kind ``scan`` of (bucket - n)
over the sum of bucket, in percent.  Each padded step still selects the
whole arena."""
from bench import spanread


def read(run):
    recs = spanread.records(run)
    if recs is None:
        return None
    scans = [r.attrs for r in recs if r.name == "enoki.dispatch"
             and (r.attrs or {}).get("kind") == "scan"]
    steps = sum(a["bucket"] for a in scans)
    if not steps:
        return None
    return 100.0 * sum(a["bucket"] - a["n"] for a in scans) / steps
