"""Device time of a benchmark cell by the program's own scopes, by hand:

    chiprun --chips 1 --timeout 1500 -- python3 tools/scope_table.py \
        --workload gpt2-large-decode-sat --seed 2147483001

runs the cell's traced run (``perfbench/run.py --trace 1``) in this process
with its trace kept, then asks ``mxnet_tpu.observability.device_scopes`` for
the table of the session's programs and prints device milliseconds by
program kind and by scope, the share the resolver could name, the largest
events it could not, what building the table cost, and a line a program,
"argument copies": every ``copy`` / ``transpose`` in its compiled text whose
operand is a program argument (a weight turned round in every call:
docs/generation.md "A weight reaches its product as stored").  ``--keep DIR``
also writes the table as JSON, the programs' compiled texts and the trace
there (gzip).  The cells whose metric set the benchmark's tests pin get
their by-scope tables in PERF.md section 5 this way.
"""
import argparse
import gzip
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def argument_copies_line(program, min_mb=1.0) -> str:
    """"<kind>: argument copies: n, MB" of one ``ProgramTable``, and under
    it each copy of ``min_mb`` or more: its bytes, scope and argument."""
    copies = [c for c in program.argument_copies
              if c.bytes >= min_mb * 2 ** 20]
    lines = [f"{program.kind or program.module}: argument copies: "
             f"{len(copies)}, {sum(c.bytes for c in copies) / 1e6:.1f} MB"]
    lines += [f"  {c.bytes / 1e6:8.1f} MB  {c.scope or '(unscoped)':30s} "
              f"{c.argument}  (%{c.name})" for c in copies]
    return "\n".join(lines)


def report(cell, keep=None, top=60, out=sys.stdout):
    """The part after the run: read ``.perfbench_trace/<cell>``."""
    from mxnet_tpu.observability import device_scopes as ds
    from perfbench import trace_reduce

    path = trace_reduce.find_xplane(os.path.join(ROOT, ".perfbench_trace",
                                                 cell))
    t0 = time.perf_counter()
    table = ds.table()
    built = time.perf_counter() - t0
    t0 = time.perf_counter()
    summary = ds.device_table(path, table)
    print(f"scope table of {cell}: {len(table.programs)} programs "
          f"{[p.kind for p in table.programs]} built in {built:.2f} s "
          f"({ds.build_stats()}); trace read and resolved in "
          f"{time.perf_counter() - t0:.2f} s", file=out)
    print(ds.format_table(summary, top=top), file=out)
    for program in table.programs:
        print(argument_copies_line(program), file=out)
    print("unresolved events, largest first:", file=out)
    for name, ms in sorted(summary["unresolved"].items(),
                           key=lambda kv: -kv[1])[:25]:
        print(f"  {ms:10.3f} ms  {name[:220]}", file=out)
    if keep:
        by_layer = ds.rollup(summary["by_scope"], layers=True)
        os.makedirs(keep, exist_ok=True)
        with open(os.path.join(keep, "scopes.json"), "w") as f:
            json.dump({"cell": cell, "busy_ms": summary["busy_ms"],
                       "resolved_ms": summary["resolved_ms"],
                       "planes": summary["planes"], "built_s": built,
                       "by_kind": summary["by_kind"],
                       "by_scope": [[*k, v] for k, v in sorted(
                           by_layer.items(), key=lambda kv: -kv[1])]}, f,
                      indent=1)
        # (the table's programs are the session's, in its order)
        for i, (source, program) in enumerate(zip(ds.sources(),
                                                  table.programs)):
            with gzip.open(os.path.join(
                    keep, f"program_{i}_{program.kind}.txt.gz"), "wt") as f:
                f.write(source.thunk())
        with open(path, "rb") as src, gzip.open(
                os.path.join(keep, "trace.xplane.pb.gz"), "wb") as dst:
            shutil.copyfileobj(src, dst)
    return summary


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    os.environ["PERFBENCH_TRACE_KEEP"] = "1"
    from perfbench import run

    argv = ["--workload", args.workload, "--seed", str(args.seed),
            "--trace", "1"]
    if args.seconds is not None:
        argv += ["--seconds", str(args.seconds)]
    run.main(argv)
    report(args.workload, args.keep)


if __name__ == "__main__":
    main()
