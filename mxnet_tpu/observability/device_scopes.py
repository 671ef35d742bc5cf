"""Device scopes: which part of the program a device operation belongs to.

The program names its device work with ``jax.named_scope`` while it is
traced (docs/observability.md "Device scopes": the operator and node of a
symbolic graph, ``optimizer.update``, ``kvstore.allreduce``, a serving
program's kind, ``layer<i>`` > ``attn.proj`` ...).  The names cost nothing
once a program is compiled; they arrive in the COMPILED HLO as each
instruction's ``metadata={op_name="jit(f)/transpose(jvp(Convolution))/
conv1/conv_general_dilated"}``.  A profiler's device event is named after
the instruction (its whole HLO text on a TPU, the bare instruction name on
the CPU), so the compiled text maps an event to its scope.  This module is
that map.

Nothing is built until asked.  The programs that can say their compiled
text (:meth:`Executor.device_programs`,
:meth:`GenerationPrograms.device_programs`) :func:`register` themselves
weakly, once a program; ``mx.profiler`` marks its session
(:func:`session_start` / :func:`session_stop`: the launch counts say which
programs ran in it, and the stop keeps the thunks of those, not their
owners, for a reader that comes after the owners are gone);
:func:`table` compiles each such program again from its recorded argument
shapes — through the persistent compile cache where one is on — and
parses the text.  ``mx.profiler.dumps()`` and the benchmark's reducers
ask; nobody else does.
"""
from __future__ import annotations

import functools
import re
import threading
import time
import weakref
from collections import namedtuple
from typing import Dict, Iterable, List, Optional, Sequence

__all__ = ["register", "text_thunk", "session_start", "session_stop",
           "sources", "table", "resolve", "parse_op_name", "stale",
           "device_table",
           "build_stats", "Resolved", "ArgumentCopy", "Table", "ProgramTable",
           "UNSCOPED", "reset"]

#: what :func:`resolve` returns: the program's kind (``fused_step``,
#: ``decode``, ``prefill`` ...), the scope path below it (``"Convolution/
#: conv1"``, ``"layer3/moe.combine"``; ``""``: the program's own
#: operation outside every scope) and ``"forward"`` or ``"backward"``
Resolved = namedtuple("Resolved", "kind scope direction")

#: one entry of :attr:`ProgramTable.argument_copies`: the instruction, the
#: bytes of its result, its scope (``""``: none) and the argument's name as
#: the program's caller spelled it (``"params['l0_wq']"``)
ArgumentCopy = namedtuple("ArgumentCopy", "name bytes scope argument")

#: the row of device time that resolves to nothing
UNSCOPED = "(unscoped)"

# components of an ``op_name`` that jax puts there itself
_STRUCTURE = re.compile(
    r"^(jit|pjit|shard_map|while|cond|body|scan|checkpoint|remat\w*|"
    r"closed_call|core_call|custom_jvp\w*|custom_vjp\w*|branch_\d+_fun|"
    r"rematted_computation)(\(.*\))?$|->")     # ... or an einsum's spec
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_LOC = re.compile(r'loc\("([^"/][^"]*)"')    # a name stack (no file's path)
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_PRODUCT = re.compile(r"[\]\}\)] (convolution|dot)\(")
_OPCODE = re.compile(r"[\]\}\)] ([\w\-]+)\(")
_PARAMETER = re.compile(r" parameter\((\d+)\)")
# what hands an operand on as it is, moved or cut but not computed with: a
# weight's way from the program's argument to the copy that re-lays it out
# (its prefetch is a ``slice-start`` / ``-done`` a piece under a
# ``ConcatBitcast`` custom call, or a ``copy-start`` / ``-done``)
_MOVES = frozenset({"bitcast", "reshape", "copy", "transpose", "slice",
                    "copy-start", "copy-done", "slice-start", "slice-done",
                    "get-tuple-element"})
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
             "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
             "f64": 8}          # anything else: 4
_OPERAND = re.compile(r"%([\w.\-]+)")
_SHAPE = re.compile(r"\b[a-z]\w*\[[\d,]*\]")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*\))?\s*(?:->"
                          r".*)?\{\s*$")

_lock = threading.Lock()
_recompile_lock = threading.Lock()
_owners: "weakref.WeakSet" = weakref.WeakSet()
_marks: Dict[tuple, int] = {}       # (owner id, label, key) -> launches
_session: Optional[list] = None     # [_Source] of the last profiler session
_cache: Dict[int, "ProgramTable"] = {}      # id(thunk) -> its parsed text
_built = {"programs": 0, "seconds": 0.0, "stale": 0}


class _Source:
    """One program that can say its text: ``kind`` (None: the text's own
    outermost scope says it), a hashable ``key`` among its owner's
    programs, ``launches`` (None: not counted) and the ``thunk`` that
    returns the optimised HLO text."""
    __slots__ = ("kind", "key", "launches", "thunk")

    def __init__(self, kind, key, launches, thunk):
        self.kind, self.key = kind, key
        self.launches, self.thunk = launches, thunk


def register(owner) -> None:
    """``owner.device_programs()`` yields ``(kind, key, launches, thunk)``
    for each of its compiled programs (:class:`_Source`); the owner is held
    weakly.  Called once an owner or a program, never a step."""
    _owners.add(owner)


def _components(stacks) -> set:
    """Every scope component of the name stacks (``a/b/c``), jax's
    transformations and structure taken off as :func:`parse_op_name`
    does."""
    out = set()
    for stack in stacks:
        out.update(parse_op_name(stack)[0].split("/"))
    out.discard("")
    return out


def stale(lowered, text: str) -> bool:
    """Whether the compiled ``text`` carries another build's scopes than
    ``lowered``, the fresh lowering it was compiled from.  jax's
    persistent compile cache leaves metadata out of its key
    (``jax_compilation_cache_include_metadata_in_key``: "executables
    loaded from the cache may have stale metadata"), so an entry that a
    build with other scopes (or none) filled is a hit for the same
    program, and its text says that build's names.  Told by the names
    alone: a scope in the text that no location of the lowering says (a
    lowering spells a stack in pieces, a piece a called function, so
    whole paths cannot be compared), or no scope at all where the
    lowering has some."""
    fresh = _components(
        name + "/." for name in _LOC.findall(lowered.as_text(debug_info=True)))
    if not fresh:
        return False
    have = _components(n for n in _OP_NAME.findall(text) if "/" in n)
    return not have or not have <= fresh


def compiled_text(jitted, avals) -> str:
    """The optimised HLO text of ``jitted`` at ``avals``: a compile, read
    from the persistent cache where one is on.  Where that entry's scopes
    are :func:`stale` the program is compiled once more under a key that
    covers the metadata (a backend compile the first time, an entry of its
    own in the cache from then on; ``build_stats()["stale"]`` counts
    them).  The option passed along is one jax itself leaves out of the
    cache key as not affecting the result: it is there because jax keeps
    the first executable of a lowering in memory, by its options."""
    import jax

    lowered = jitted.lower(*avals)
    text = lowered.compile().as_text()
    if not stale(lowered, text):
        return text
    flag = "jax_compilation_cache_include_metadata_in_key"
    with _lock:
        _built["stale"] += 1
    with _recompile_lock:       # one at a time: the flag is the process's
        was = getattr(jax.config, flag)
        jax.config.update(flag, True)
        try:
            return jitted.lower(*avals).compile(compiler_options={
                "xla_dump_disable_metadata": False}).as_text()
        finally:
            jax.config.update(flag, was)


def text_thunk(jitted, args):
    """What an owner hands :func:`register`'s reader for one program:
    ``functools.partial(compiled_text, jitted, shapes of args)``.  It
    holds the jitted function and shapes (with the placement of committed
    arrays), never an array or the owner."""
    import jax

    avals = jax.tree_util.tree_map(
        lambda a: a if isinstance(a, jax.ShapeDtypeStruct)
        else jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=a.sharding if getattr(a, "committed", False) else None),
        args)
    return functools.partial(compiled_text, jitted, avals)


def _live_sources():
    out = []
    for owner in list(_owners):
        for kind, key, launches, thunk in owner.device_programs():
            out.append((id(owner), _Source(kind, key, launches, thunk)))
    return out


def session_start() -> None:
    """``mx.profiler.start()``: remember every live program's launches."""
    global _session
    with _lock:
        _marks.clear()
        _session = None
        for oid, s in _live_sources():
            if s.launches is not None:
                _marks[oid, s.kind, s.key] = s.launches


def session_stop() -> None:
    """``mx.profiler.stop()``: keep the thunks of the programs that ran
    since :func:`session_start` (or whose launches nobody counts), so that
    the session can be read after its programs' owners are gone."""
    global _session
    with _lock:
        kept = []
        for oid, s in _live_sources():
            since = _marks.get((oid, s.kind, s.key), 0)
            if s.launches is None or s.launches > since:
                kept.append(s)
        _session = kept


def reset() -> None:
    """Forget the session, the marks and every parsed text (tests)."""
    global _session
    with _lock:
        _marks.clear()
        _cache.clear()
        _session = None
        _built.update(programs=0, seconds=0.0, stale=0)


# -- parsing ------------------------------------------------------------------

def parse_op_name(op_name: str):
    """``"jit(f)/transpose(jvp(Convolution))/conv1/conv_general_dilated"``
    -> ``("Convolution/conv1", "backward")``: the program's own scopes,
    jax's transformations peeled off (``transpose`` anywhere: backward),
    jax's own structure (``jit(...)``, ``shard_map``, ``while/body`` ...)
    and the primitive's name, last, left out.  (Where the compiler made
    one operation of several it joins their names with ``;``: the first
    one's scope.)"""
    parts = [p for p in op_name.partition(";")[0].split("/") if p]
    scope, direction = [], "forward"
    for part in parts[:-1]:
        while True:
            m = _WRAPPED.match(part)
            if not m or m.group(1) in ("jit", "pjit"):
                break
            if m.group(1) == "transpose":
                direction = "backward"
            part = m.group(2)
        if part and not _STRUCTURE.search(part):
            scope.append(part)
    return "/".join(scope), direction


def _signature(text: str):
    """``(instruction name, result shapes)`` of one instruction's text, in
    the compiled module's spelling or a device trace's (which prints the
    operands' shapes too and no metadata): the part both agree on."""
    m = _INSTR.match(text)
    if not m:
        return text.strip().lstrip("%"), None
    rest, depth, cut = text[m.end():], 0, None
    for i, ch in enumerate(rest):      # the result ends where the opcode,
        if ch in "([{":                # a word before "(" at depth 0, starts
            if ch == "(" and depth == 0 and i and (rest[i - 1].isalnum()
                                                   or rest[i - 1] in "-_"):
                cut = rest.rfind(" ", 0, i)
                break
            depth += 1
        elif ch in ")]}":
            depth -= 1
    result = rest[:cut] if cut and cut > 0 else ""
    return m.group(1), tuple(_SHAPE.findall(result))


class ProgramTable:
    """One compiled program's instructions: ``name -> (result shapes,
    Resolved or None)``.  An instruction the compiler made and gave no
    ``op_name`` (a copy, a slice's ``-done``) takes the scope of the first
    instruction that waits for it, or failing that of the one that made
    its operand (a layout copy of a result).  A fusion is one event over several
    operations: one around a convolution or a matrix product goes to that
    product's scope (the epilogue the compiler fused behind it — a
    BatchNorm's apply, a cast, a parameter's update — is counted with it),
    any other to the scope most of its body's operations have (its own
    ``op_name`` is its root's alone: a loop over a BatchNorm's backward
    pass that ends in a cast would read as the cast).

    ``argument_copies`` lists what the text re-lays-out of the program's
    own arguments (:class:`ArgumentCopy`)."""

    def __init__(self, kind: Optional[str], text: str):
        self.module = ""
        self.instrs: Dict[str, tuple] = {}
        self.order: Dict[str, int] = {}     # ENTRY's instructions, as run
        own: Dict[str, Optional[str]] = {}      # name -> op_name or None
        users: Dict[str, List[str]] = {}
        operands: Dict[str, List[str]] = {}
        calls: Dict[str, str] = {}
        members: Dict[str, List[str]] = {}      # computation -> its names
        products = set()                        # convolutions, dots
        shapes: Dict[str, tuple] = {}
        opcode: Dict[str, str] = {}
        argument: Dict[str, str] = {}       # ENTRY's parameters, as named
        inside: Dict[str, tuple] = {}       # a body's parameter -> (body, k)
        comp, entry = None, False
        for line in text.splitlines():
            if not self.module:
                m = _MODULE.match(line)
                if m:
                    self.module = m.group(1)
                    continue
            m = _INSTR.match(line)
            if not m:
                c = _COMPUTATION.match(line.strip())
                if c:
                    comp, entry = c.group(1), line.startswith("ENTRY")
                continue
            name = m.group(1)
            if entry:       # a scheduled module's text is in running order
                self.order[name] = len(self.order)
            head = line.split(", metadata={", 1)[0]
            shapes[name] = _signature(head)[1]
            op = _OP_NAME.search(line)
            # (an op_name without a path is an argument's name, which a
            # copy of that argument inherits: no scope)
            own[name] = op.group(1) if op and "/" in op.group(1) else None
            if own[name] and _PRODUCT.search(head):
                products.add(name)
            code = _OPCODE.search(head)
            opcode[name] = code.group(1) if code else ""
            if opcode[name] == "custom-call" and \
                    'custom_call_target="ConcatBitcast"' in head:
                opcode[name] = "bitcast"
            elif opcode[name] == "parameter":
                k = int(_PARAMETER.search(head).group(1))
                if entry:
                    argument[name] = op.group(1).replace("\\'", "'") \
                        if op else name
                else:
                    inside[name] = (comp, k)
            members.setdefault(comp, []).append(name)
            called = _CALLS.search(line)
            if called:
                calls[name] = called.group(1)
            operands[name] = _OPERAND.findall(head[m.end():])
            for operand in operands[name]:
                users.setdefault(operand, []).append(name)

        def scope_of(name, depth=0):
            body = members.get(calls.get(name, ""), ())
            votes: Dict[tuple, list] = {}       # scope -> [count, op_name]
            for inner in body:
                op = own.get(inner)
                if not op:
                    continue
                if inner in products:
                    return op
                vote = votes.setdefault(parse_op_name(op), [0, op])
                vote[0] += 1
            if votes:
                return max(votes.values(), key=lambda v: v[0])[1]
            op = own.get(name)
            if op:
                return op
            if depth > 4:
                return None
            # ... whoever waits for it, else whoever made what it moves
            for other in users.get(name, []) + operands.get(name, []):
                found = scope_of(other, depth + 1)
                if found:
                    return found
            return None

        fusions: Dict[str, List[str]] = {}      # a body -> who calls it
        for name, called in calls.items():
            if opcode[name] in ("fusion", "call"):
                fusions.setdefault(called, []).append(name)

        def argument_of(name, depth=0):
            """The ENTRY parameter that ``name`` hands on unchanged but for
            its layout, or None: a ``%param_N`` of a fused computation is
            that fusion's operand, followed to whoever called it."""
            while depth < 32:
                depth += 1
                if name in argument:
                    return name
                if name in inside:
                    body, k = inside[name]
                    for caller in fusions.get(body, ()):
                        if k < len(operands[caller]):
                            found = argument_of(operands[caller][k], depth)
                            if found:
                                return found
                    return None
                if opcode.get(name) not in _MOVES or not operands[name]:
                    return None
                name = operands[name][0]
            return None

        parsed = {name: parse_op_name(op) for name, op in
                  ((name, scope_of(name)) for name in own) if op}
        if kind is None:
            # a serving program's outermost scope is its kind
            heads: Dict[str, int] = {}
            for scope, _ in parsed.values():
                head = scope.partition("/")[0]
                if head:
                    heads[head] = heads.get(head, 0) + 1
            kind = max(heads, key=heads.get) if heads else ""
        self.kind = kind
        for name in own:
            if name not in parsed:
                self.instrs[name] = (shapes[name], None)
                continue
            scope, direction = parsed[name]
            head, _, below = scope.partition("/")
            if head == kind:        # ... said once
                scope = below
            self.instrs[name] = (shapes[name],
                                 Resolved(kind, scope, direction))
        #: an :class:`ArgumentCopy` for every ``copy`` and ``transpose`` —
        #: executed alone or inside a fusion — of a program ARGUMENT (a
        #: weight, a pool), straight or through its prefetch: a re-layout
        #: the program pays in every call, for an operand that never
        #: changes (docs/generation.md "A weight reaches its product as
        #: stored"); largest first
        self.argument_copies: List[ArgumentCopy] = []
        for name, code in opcode.items():
            if code not in ("copy", "transpose") or not operands[name]:
                continue
            source = argument_of(operands[name][0])
            if source is None or not shapes[name]:
                continue
            dtype, _, dims = shapes[name][0].partition("[")
            n = _ITEMSIZE.get(dtype, 4)
            for dim in dims.rstrip("]").split(","):
                n *= int(dim or 1)
            resolved = self.instrs[name][1]
            self.argument_copies.append(ArgumentCopy(
                name, n, resolved.scope if resolved else "",
                argument[source]))
        self.argument_copies.sort(key=lambda c: -c.bytes)

    def lookup(self, name, shapes=None):
        """The entry of instruction ``name``, if the program has it (and,
        where the event said its result ``shapes``, with those)."""
        hit = self.instrs.get(name)
        if hit is None or (shapes is not None and hit[0] is not None
                           and hit[0] != shapes):
            return None
        return hit


class Table:
    """The parsed programs of a session (or of everything registered)."""

    def __init__(self, programs: Sequence[ProgramTable]):
        self.programs = list(programs)
        self._found: Dict[tuple, list] = {}     # a trace repeats its names

    def _having(self, event_name, module=None):
        """``(instruction name, [(program, Resolved or None)])`` of the
        programs that hold the event's instruction."""
        got = self._found.get((event_name, module))
        if got is None:
            name, shapes = _signature(event_name)
            found = []
            for p in self.programs:
                if module and p.module and p.module != module:
                    continue
                hit = p.lookup(name, shapes)
                if hit is not None:
                    found.append((p, hit[1]))
            got = self._found[event_name, module] = (name, found)
        return got

    def resolve(self, event_name: str, module: Optional[str] = None):
        """The :class:`Resolved` of a device event by its name alone — a
        TPU event's whole HLO text or a bare instruction name — or None:
        the name is in no program, has no scope there, or means different
        scopes in two programs."""
        found = {r for _, r in self._having(event_name, module)[1]}
        return found.pop() if len(found) == 1 else None

    def resolve_stream(self, names: Sequence[str],
                       modules: Optional[Sequence[str]] = None) -> list:
        """:func:`resolve` for the events of ONE device line in the order
        they ran.  Programs run one after the other there, so successive
        events narrow which program is running; another run has begun
        where no program holds an event and all the ones before it, or
        where the event would run backwards in the schedule of every
        program still possible.  An event whose name two programs scope
        differently is then resolved by the run it lies in."""
        out: list = [None] * len(names)
        run: list = []              # [(index, [(program, resolved)])]
        cands, at = None, {}        # at: program -> its schedule's place

        def flush():
            for i, found in run:
                mine = {r for p, r in found if cands is None or p in cands}
                out[i] = mine.pop() if len(mine) == 1 else None

        for i, event in enumerate(names):
            name, found = self._having(event, modules[i] if modules else None)
            if not found:
                continue
            has = {p for p, _ in found}
            narrowed = has if cands is None else cands & has
            if narrowed and all(p.order.get(name, len(p.order)) <= at.get(
                    p, -1) for p in narrowed):
                narrowed = set()
            if not narrowed:
                flush()
                run, narrowed, at = [], has, {}
            cands = narrowed
            for p in cands:
                if name in p.order:
                    at[p] = p.order[name]
            run.append((i, found))
        flush()
        return out


def sources() -> list:
    """The programs :func:`table` reads, in its order: the last profiler
    session's (see :func:`session_stop`), or every registered program's
    where no session was marked.  Each has ``kind``, ``key``, ``launches``
    and ``thunk`` (the optimised HLO text when called)."""
    with _lock:
        return list(_session) if _session is not None \
            else [s for _, s in _live_sources()]


def table() -> Table:
    """The :class:`Table` of :func:`sources`.  Compiles each program it
    has not parsed yet (a read of the persistent compile cache where one is
    on); :func:`build_stats` says what that took."""
    programs = []
    for s in sources():
        key = id(s.thunk)
        got = _cache.get(key)
        if got is None or got[0] is not s.thunk:
            t0 = time.perf_counter()
            got = (s.thunk, ProgramTable(s.kind, s.thunk()))
            with _lock:
                _cache[key] = got
                _built["programs"] += 1
                _built["seconds"] += time.perf_counter() - t0
        programs.append(got[1])
    return Table(programs)


def build_stats() -> dict:
    """``{"programs", "seconds", "stale"}``: how many compiled texts
    :func:`table` has built and parsed in this process, the seconds that
    took, and how many of them were compiled once more because the
    persistent cache's entry carried another build's scopes
    (:func:`stale`)."""
    with _lock:
        return dict(_built)


def resolve(event_name: str, module: Optional[str] = None):
    """:meth:`Table.resolve` on :func:`table`."""
    return table().resolve(event_name, module)


# -- the operator's reading ---------------------------------------------------

def self_times(events: Iterable[tuple]) -> list:
    """``[(start, end, ...)]`` -> ``[(event, its OWN nanoseconds)]`` by
    start: an event's duration less the events nested in it (a ``while``
    holds its body's operations), so that the sum is the line's busy
    time."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    own = [e[1] - e[0] for e in events]
    stack: list = []
    for i, e in enumerate(events):
        while stack and events[stack[-1]][1] <= e[0]:
            stack.pop()
        if stack and e[1] <= events[stack[-1]][1]:
            own[stack[-1]] -= e[1] - e[0]
        stack.append(i)
    return [(e, max(0, t)) for e, t in zip(events, own)]


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` jax's profiler wrote under ``trace_dir``."""
    import glob
    import os

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return files[-1] if files else None


def device_table(xplane_path: str, tbl: Optional[Table] = None) -> dict:
    """Device milliseconds of a ``.xplane.pb`` by scope and by program
    kind: ``{"by_scope": {(kind, scope, direction): ms}, "by_kind": {kind:
    ms}, "unresolved": {event name: ms}, "busy_ms", "resolved_ms",
    "planes"}``, the mean over the device planes.  Device planes are
    ``/device:TPU:<n>`` (their line ``XLA Ops``); a trace without one (a
    CPU run) is read from the host plane's events that carry an
    ``hlo_op``."""
    import jax

    tbl = tbl or table()
    data = jax.profiler.ProfileData.from_file(xplane_path)
    lines = []                          # [[(start, end, name, module)]]
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name, None)
                   for ln in plane.lines if ln.name == "XLA Ops"
                   for e in ln.events]
            if evs:
                lines.append(evs)
    planes = len(lines)
    if not lines:
        for plane in data.planes:
            if not plane.name.startswith("/host:CPU"):
                continue
            for ln in plane.lines:
                evs = []
                for e in ln.events:
                    stats = dict(e.stats)
                    if "hlo_op" in stats:
                        evs.append((e.start_ns, e.start_ns + e.duration_ns,
                                    str(stats["hlo_op"]),
                                    str(stats.get("hlo_module") or "")))
                if evs:
                    lines.append(evs)
        planes = 1 if lines else 0
    by_scope: Dict[tuple, float] = {}
    unresolved: Dict[str, float] = {}
    busy = resolved = 0.0
    for evs in lines:
        evs, own = zip(*self_times(evs))
        found = tbl.resolve_stream([e[2] for e in evs],
                                   [e[3] for e in evs]
                                   if evs[0][3] is not None else None)
        for e, ns, r in zip(evs, own, found):
            busy += ns
            if r is not None:
                resolved += ns
            else:
                unresolved[e[2]] = unresolved.get(e[2], 0.0) + ns
            key = tuple(r) if r is not None else (UNSCOPED, "", "")
            by_scope[key] = by_scope.get(key, 0.0) + ns
    per = 1e6 * max(1, planes)
    by_scope = {k: v / per for k, v in by_scope.items()}
    unresolved = {k: v / per for k, v in unresolved.items()}
    by_kind: Dict[str, float] = {}
    for (kind, _, _), ms in by_scope.items():
        by_kind[kind] = by_kind.get(kind, 0.0) + ms
    return {"by_scope": by_scope, "by_kind": by_kind,
            "unresolved": unresolved, "busy_ms": busy / per,
            "resolved_ms": resolved / per, "planes": planes}


def rollup(by_scope: Dict[tuple, float], layers: bool = False,
           depth: Optional[int] = None) -> dict:
    """``by_scope`` folded for a table a person reads: forward and
    backward kept apart, ``layer<i>/`` taken off the front of a serving
    scope unless ``layers``, and only the first ``depth`` components kept
    (1: a symbolic program by operator)."""
    out: Dict[tuple, float] = {}
    for (kind, scope, direction), ms in by_scope.items():
        if not layers:
            scope = re.sub(r"^layer\d+/?", "", scope)
        if depth:
            scope = "/".join(scope.split("/")[:depth])
        key = (kind, scope, direction)
        out[key] = out.get(key, 0.0) + ms
    return out


def format_table(summary: dict, top: int = 40) -> str:
    """The device section of ``mx.profiler.dumps()``."""
    lines = [f"Device time by program kind ({summary['planes']} device "
             f"plane(s), busy {summary['busy_ms']:.3f} ms, resolved "
             f"{100.0 * summary['resolved_ms'] / summary['busy_ms']:.1f}%)"
             if summary["busy_ms"] else "Device time: no device operation "
             "in the trace",
             f"{'Kind':<40}{'Total(ms)':>15}"]
    for kind, ms in sorted(summary["by_kind"].items(), key=lambda kv: -kv[1]):
        lines.append(f"{kind:<40}{ms:>15.3f}")
    for title, depth in (("Kind / operator or layer part (direction)", 1),
                         ("Kind / scope (direction)", None)):
        lines.append(f"{title:<64}{'Total(ms)':>15}")
        rows = sorted(rollup(summary["by_scope"], depth=depth).items(),
                      key=lambda kv: -kv[1])
        for (kind, scope, direction), ms in rows[:top]:
            name = kind if kind == UNSCOPED else \
                f"{kind} / {scope or '(program)'} ({direction})"
            lines.append(f"{name:<64}{ms:>15.3f}")
    return "\n".join(lines)
