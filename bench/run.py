"""Run one cell of the benchmark once, on the chip this process finds.

    python bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (a configuration under a traffic mix) is looked up by name in
``BENCHMARK.json``.  The last line of standard output is the result, one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` a ``breakdown`` of the trace, and last the
``checks``, each number compared beside its limit; the same numbers end
standard error.  Exits non-zero, printing no result, when JAX finds no TPU,
fewer chips than the cell asks for, or a chip missing from
``bench/peaks.json``.  Traces and scratch files go under
``bench/.out/<workload>/``; JAX's compile cache under ``.jax_cache/``,
both inside the checkout.
"""
import time

T_START = time.perf_counter()

import argparse                 # noqa: E402
import pathlib                  # noqa: E402
import shutil                   # noqa: E402
import sys                      # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import spec
    try:
        cell = spec.load_cell(ROOT, args.workload)
    except spec.SpecError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"run.py: no TPU: the default device is {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"run.py: the cell needs {cell.chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return 3
    try:
        peaks = spec.load_peaks(dev.device_kind)
    except spec.SpecError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    spec.use_compile_cache(ROOT)

    from bench import harness
    out_dir = ROOT / "bench" / ".out" / args.workload
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)
    try:
        result = harness.run_cell(cell, seed=args.seed % (1 << 63),
                                  seconds=args.seconds,
                                  trace=bool(args.trace), peaks=peaks,
                                  t_start=T_START, out_dir=str(out_dir))
    finally:
        shutil.rmtree(out_dir / "trace", ignore_errors=True)
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
