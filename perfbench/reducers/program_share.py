"""Share of the device's busy time, in the traced slice, that one kind of
program took, where programs of several kinds run one after the other on
one device and every run of a program holds a kernel whose name tells its
kind: 100 x device seconds of the operations that belong to runs holding
a ``num`` kernel / device seconds of all operations.

An operation belongs to the kind of the ``markers`` kernels around it.
Between a kernel of one kind and the next kernel of another, one program
ends and the other begins: the cut is at the longest pause between two
operations there (the device waits for the next dispatch).
params: ``num``, ``markers`` (regular expressions; ``markers`` matches
every kind's kernel).  No such kernel in the trace: no metric."""
import re


def reduce(params, src):
    if src.trace is None:
        return None
    num_rx, any_rx = re.compile(params["num"]), re.compile(params["markers"])
    mine = total = 0
    for evs in src.trace.devices.values():
        marks = [(i, bool(num_rx.search(n))) for i, (_, _, n)
                 in enumerate(evs) if any_rx.search(n)]
        if not marks:
            continue
        kind = [marks[0][1]] * len(evs)
        for (i, k), (j, k2) in zip(marks, marks[1:] + [(len(evs), None)]):
            cut = j
            if k2 is not None and k2 != k:
                cut = max(range(i + 1, j + 1),
                          key=lambda t: evs[t][0] - evs[t - 1][1])
            kind[i:cut] = [k] * (cut - i)
            kind[cut:j] = [k2] * (j - cut)
        mine += sum(e - s for (s, e, _), k in zip(evs, kind) if k)
        total += sum(e - s for s, e, _ in evs)
    return 100.0 * mine / total if total else None
