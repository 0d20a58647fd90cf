"""Host time the serving path spends per request answered: the self time
of every ``enoki.submit`` and ``enoki.turn`` in the window (its duration
less the ``enoki.fetch`` and ``enoki.sleep`` spans inside it, the waits
on the device and for work) over the requests answered in the window, in
microseconds."""
from bench import spanread

HOST = ("enoki.submit", "enoki.turn")
WAITS = ("enoki.fetch", "enoki.sleep")


def read(run):
    recs = spanread.records(run)
    if recs is None:
        return None
    parent = {r.id: r.parent for r in recs}
    host = {r.id: r.end - r.start for r in recs if r.name in HOST}
    for r in recs:
        if r.name not in WAITS:
            continue
        p = r.parent
        while p and p not in host:
            p = parent.get(p, 0)
        if p:
            host[p] -= r.end - r.start
    answered = sum(1 for r in recs if r.name == "enoki.request"
                   and not r.attrs["lost"] and r.end < run.t1_ns)
    if not host or not answered:
        return None
    return sum(host.values()) / 1e3 / answered
