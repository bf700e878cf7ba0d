"""Run one cell of the benchmark once.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device`` and, when
traced, ``breakdown``.  An earlier line says where the run's time went.

The cell's name is resolved to files by ``perfbench/cells.py``; this file
knows no cell, no model and no metric.  ``--rehearse`` runs the same command
on the CPU backend at the widths of ``configs/_tiny.json`` on as many
virtual devices as the cell has chips: it stamps ``device`` as cpu and
prints counts only, never a time, a rate or a share.

Exit codes: 0 a result was printed; 2 bad arguments or an unknown name;
3 the machine is not what the cell needs, or the program under test is not
in the checkout (no result is printed).
"""

import time

T0 = time.perf_counter()  # set-up is counted from here: before any heavy import

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: "
                         "run_seconds of BENCHMARK.json)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny widths; counts only")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="with --trace 1, also copy the .xplane.pb there")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    # the checkout's root holds both this package and the program under test
    sys.path.insert(0, str(ROOT))
    from perfbench import cells

    try:
        cell = cells.resolve(args.workload, BENCH_DIR,
                             config_override="_tiny" if args.rehearse else None)
    except cells.CellError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    if args.seconds is None:
        args.seconds = cells.load_benchmark(BENCH_DIR)["run_seconds"]
    if args.rehearse:
        # must precede the first jax import: the platform is fixed there
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}").strip()
    try:
        import bagua_tpu  # noqa: F401 - the system under test
    except ImportError as e:
        print(f"perfbench: the program under test is not importable from "
              f"{ROOT}: {e}", file=sys.stderr)
        return 3
    driver = cells.load_plugin("drivers", cell.traffic["driver"], BENCH_DIR)
    try:
        result = driver.run(cell, args, T0)
    except cells.DeviceError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
