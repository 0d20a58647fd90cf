"""The replica-merge kernel's share of its roofline: the bytes each call
must move (``bench.kernels.merge_bytes``) over the kernel's device time
in the trace, over the chip's HBM bandwidth from ``bench/peaks.json``.
The kernel moves no arithmetic worth the name, so bandwidth bounds it."""
from bench.kernels import merge_bytes

KERNEL = "enoki_merge_rows"


def read(run):
    if run.trace is None:
        return None
    seconds, calls = run.trace.ops_matching(KERNEL)
    if not calls or seconds <= 0:
        return None
    a = run.arena
    need = calls * merge_bytes(a["slots"], a["width"], a["itemsize"])
    return 100.0 * need / seconds / run.peaks["hbm_bytes_per_s"]
