"""Read the numbers that ``correct`` compares, for the program and for its
control, at a cell's own size and load, in one process on the chip.

    python bench/control.py --workload <name> --seconds <s> \
        --seeds 1 2 3 ... --control-seeds 7 8 9

The control is the program with the keygroup's records held one precision
below the configuration's float32: its own bfloat16 arena
(``KeygroupSpec.dtype``).  Each run is a whole benchmark run (deploy,
fill, warm, window, drain, check); one JSON line per run on standard
output with the seed, which side ran, ``correct`` and every count with
its limit.  The benchmark's own runs never run the control.
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CONTROL_DTYPE = "bfloat16"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    from bench import spec
    cell = spec.load_cell(ROOT, args.workload)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("control.py: no TPU", file=sys.stderr)
        return 3
    peaks = spec.load_peaks(dev.device_kind)
    spec.use_compile_cache(ROOT)

    from bench import harness
    out_dir = ROOT / "bench" / ".out" / f"control-{args.workload}"
    out_dir.mkdir(parents=True, exist_ok=True)
    runs = ([(s, "program", None) for s in args.seeds]
            + [(s, "control", CONTROL_DTYPE) for s in args.control_seeds])
    for seed, side, dtype in runs:
        res = harness.run_cell(cell, seed=seed, seconds=args.seconds,
                               trace=False, peaks=peaks,
                               t_start=time.perf_counter(),
                               out_dir=str(out_dir), dtype=dtype)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "side": side, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "metrics": res["metrics"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
