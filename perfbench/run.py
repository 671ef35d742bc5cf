"""The benchmark's command:

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration and traffic files by
name, and the driver the configuration names; prints set-up and sample
lines, each number compared beside its limit, and as the last line of
standard output the one JSON object.  Exits non-zero, printing no result,
when jax finds no TPU or fewer chips than the cell asks for.
"""
import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from perfbench import harness

    seconds = args.seconds if args.seconds is not None \
        else harness.benchmark()["run_seconds"]
    ctx = harness.make_context(args.workload, args.seed, seconds,
                               args.trace, T_PROC0)
    print(f"compile cache: {harness.enable_compile_cache()}", flush=True)
    harness.CompileClock.install()
    devs = harness.check_devices(ctx)
    driver = importlib.import_module(
        "perfbench.drivers." + ctx.config["driver"])
    outcome = driver.run(ctx)
    print(json.dumps(harness.result_line(ctx, devs, outcome)), flush=True)


if __name__ == "__main__":
    main()
