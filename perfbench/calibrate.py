"""Read the numbers `correct` compares, over several seeds in one process
(set-up is most of a run, and the compiled programs are shared):

    python3 perfbench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 5 [--control]

Without ``--control`` it drives the cell as ``run.py`` does, for a short
window, and prints each seed's numbers: the largest is what a limit has to
stay above.  With ``--control`` the reference in the next lower precision
stands in the program's place: the smallest is what a limit has to stay
below.  The limits themselves are in the drivers; PERF.md holds the
readings they were set from.
"""
import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    from perfbench import harness

    print(f"compile cache: {harness.enable_compile_cache()}", flush=True)
    harness.CompileClock.install()
    table = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = harness.make_context(args.workload, seed, args.seconds, False,
                                   time.perf_counter(),
                                   hooks={"control": args.control})
        harness.check_devices(ctx)
        driver = importlib.import_module(
            "perfbench.drivers." + ctx.config["driver"])
        outcome = driver.run(ctx)
        ok = harness.print_checks(outcome["checks"])
        print(f"seed {seed}: correct={ok} "
              f"{ {k: v for k, v in outcome.get('e2e', {}).items()} }",
              flush=True)
        for name, value, _, _ in outcome["checks"]:
            if isinstance(value, float):
                table.setdefault(name, []).append(value)
    for name, values in table.items():
        print(f"{'control' if args.control else 'program'} {name}: "
              f"min={min(values):.6g} max={max(values):.6g} "
              f"n={len(values)} all={[round(v, 6) for v in values]}",
              flush=True)


if __name__ == "__main__":
    main()
