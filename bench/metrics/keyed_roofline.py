"""The fold's request-keyed ops as a share of the HBM roofline: the bytes
those ops must move over the device time of the fold executables
(``jit_scanned`` + ``jit_mapped``) in the trace, over the chip's HBM
bandwidth from ``bench/peaks.json``.

The ops are counted from the window's ``enoki.dispatch`` spans: ``n`` x
(``keyed_gets`` + ``keyed_sets``).  A keyed get moves its record, its
length and version (8 B) and the key it compares (4 B); a keyed set moves
the same, written.  Only what the semantics need is counted, never the
O(S) key scan a linear probe performs, so the share reads the same work
whatever implements it.  A program whose spans carry no keyed counts
reads nothing."""
from bench import spanread

FOLD_MODULES = ("jit_scanned", "jit_mapped")


def op_bytes(width: int, itemsize: int) -> int:
    """HBM bytes of one request-keyed get or set of a record."""
    return width * itemsize + 8 + 4


def read(run):
    if run.trace is None:
        return None
    recs = spanread.records(run)
    if recs is None:
        return None
    ops = sum(a["n"] * (a["keyed_gets"] + a["keyed_sets"])
              for a in (r.attrs for r in recs if r.name == "enoki.dispatch")
              if a and "keyed_gets" in a)
    seconds = run.trace.modules_matching(FOLD_MODULES)
    if not ops or seconds <= 0:
        return None
    a = run.arena
    need = ops * op_bytes(a["width"], a["itemsize"])
    return 100.0 * need / seconds / run.peaks["hbm_bytes_per_s"]
