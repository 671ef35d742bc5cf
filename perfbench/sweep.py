"""Find the highest rate an open-loop cell sustains — once, when the cell
is defined; the cell then offers a FIXED rate (about four fifths of it),
written into its traffic file.

    python3 perfbench/sweep.py --workload <cell> --rates 4,6,8,10 --seconds 20

One process, one warmed service, one window per rate.  A rate is sustained
when the requests completed per second stay within 3% of those offered and
no more requests than ``max_slots`` wait when the window closes.
"""
import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from perfbench import harness
    from perfbench.drivers import generation

    print(f"compile cache: {harness.enable_compile_cache()}", flush=True)
    harness.CompileClock.install()
    ctx = harness.make_context(args.workload, args.seed, args.seconds, False,
                               T_PROC0)
    harness.check_devices(ctx)
    svc, _, _, _ = generation.build(ctx)
    slots = svc._config.max_slots
    print("rate offered/s completed/s waiting running ttft_p50 ttft_p95 "
          "itl_p50 itl_p95 tok/s sustained", flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        w = generation.offer(ctx, svc, rate)
        offered = len(w["mine"]) / w["window_s"]
        done_in = sum(1 for r in w["records"]
                      if r.done and w["t0"] <= r.stamps[-1] < w["t1"])
        completed = done_in / w["window_s"]
        waiting = w["at_close"]["waiting"]
        ok = completed >= 0.97 * offered and waiting <= slots
        pct = harness.percentile
        print(f"{rate:g} {offered:.3f} {completed:.3f} {waiting} "
              f"{w['at_close']['running']} {pct(w['ttft'], 50):.1f} "
              f"{w['ttft_p95_ms']:.1f} {pct(w['gaps'], 50):.1f} "
              f"{w['itl_p95_ms']:.1f} {w['serve_tok_s']:.1f} "
              f"{'yes' if ok else 'NO'}", flush=True)
        # let the backlog run dry before the next rate
        deadline = time.perf_counter() + 120
        while time.perf_counter() < deadline:
            s = svc.stats()
            if not s["waiting"] and not s["running"]:
                break
            time.sleep(0.2)
    svc.stop(drain=False, timeout=60)


if __name__ == "__main__":
    main()
