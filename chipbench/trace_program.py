"""What the program's own spans and named scopes say in a profiler trace.

``trace_reduce`` reads the device lines and the benchmark's ``cb.`` spans.
This module reads the same ``.xplane.pb`` for what the program under test
names itself (``repro.obs.device``: host spans ``serve.*``, ``train.*``,
``host.gc``; named scopes in the HLO ``op_name``).  It learns those names as
constants and imports nothing of the program.

What a traced run's metrics read (``of``):

* **Launches.**  Each device program execution (a ``XLA Modules`` event) is
  paired with the host ``DoEnqueueProgram`` that issued it: the device
  event's flow in (``_ct``, ``_c``) is the host event's flow out (``_pt``,
  ``_p``); else the same ``run_id`` on the same chip.  From there the
  host's flows lead, through the events that enclose each hop, to the
  Python thread (``PJRT_LoadedExecutable_Execute`` takes its flow from the
  Python thread's ``PJRT_LoadedExecutable_Execute linkage``), where the
  spans open around the launch are its path.  A flow is its type and id
  together: ids of different types repeat.
* **Queue waits.**  A launch call that finds the device's queue full waits
  inside the runtime's ``CommonPjRtLoadedExecutable::ExecutePrepare`` until
  a program in flight ends, with no event of its own: that event's self time
  (its duration less its children's) is the wait.  On a TPU v5e it reads
  about 5 us a launch when nothing waits, and a decode step's device time
  when the host runs ahead of the chip.  The wait follows the device, not
  the host's work: each execution carries its launch call's wait
  (``Execution.wait``), and each span the waits of the launches made inside
  it (``Span.queue_wait``).
* **Self time and scopes.**  An op's self time is its duration less the ops
  nested in it on the same line (a ``while`` no longer counts its body
  again).  The trace holds each program's optimized HLO (``Hlo Proto`` in
  the ``/host:metadata`` plane, keyed by the module's name and id as the
  ``XLA Modules`` events give it); an instruction's ``op_name`` names the
  innermost scope it was emitted under.  An instruction the compiler added
  has none: it takes the op_name of the computations it calls or of the
  operands it reads, else the op_name of the op it runs inside.  Self time
  is kept by op_name, so that any named scope, nested or not, can be read
  (``per_step_ms_under``), and summed from it by scope (``SCOPES``, the
  innermost).
* **Clock offset.**  On a TPU v5e a chip's events are stamped earlier than
  the host events that issued them (on the recorded fixture every execution
  starts 1.256-1.280 ms before its enqueue).  Each chip's offset is the
  least shift under which no execution starts before its enqueue; device
  times plus the offset are host times.  The window's executions are those
  that start in it on the host's clock.

What an operator asks for besides (``read_file``, or ``read(..., gaps=True)``):

* **Causal gap labels.**  The idle gaps are ``trace_reduce``'s (the same
  gaps and lengths).  A gap ended by an execution that the host launched
  after the gap began is put down to the span path (outermost first, joined
  by ``>``) that held the host for longest between the gap's start and that
  launch; one ended by an execution launched before it began is ``queued``.
  A gap that no paired execution ends keeps the path that held the host for
  longest over the gap.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import os
import re

from chipbench import common, trace_reduce

# the program's span prefixes (repro.obs.device.SPAN_PREFIXES), beside the
# benchmark's own
PROGRAM_SPAN_PREFIXES = ("serve.", "train.", "host.")
SPAN_PREFIXES = (trace_reduce.SPAN_PREFIX,) + PROGRAM_SPAN_PREFIXES
# the program's named scopes (repro.obs.device.SCOPES)
SCOPES = ("embed", "attention", "mlp", "norm", "unembed", "layers", "loss", "grad_sync",
          "optimizer")
SCOPE = re.compile(r"(?:^|[/(])(%s)(?=[)/]|$)" % "|".join(SCOPES))
ENQUEUE = "DoEnqueueProgram"
QUEUE_WAIT = "CommonPjRtLoadedExecutable::ExecutePrepare"  # its self time: the queue wait
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
QUEUED = "queued"
NONE = "none"
MAX_HOPS = 16


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    path: tuple  # names of the spans open around it and its own, outermost first
    args: dict
    parent: int  # index of the innermost span around it, or -1
    children: list  # indices of the spans directly inside it
    queue_wait: float = 0.0  # seconds the launches made inside it waited for the queue

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Execution:
    chip: int
    module: str  # the XLA Modules event's name, "<program>(<id>)"
    start: float  # device clock
    end: float
    enqueue: float | None  # host clock: start of its DoEnqueueProgram
    path: tuple | None  # spans open on the Python thread at its launch
    span: int  # index in Reading.spans of the innermost of them, or -1
    wait: float = 0.0  # seconds its launch call waited for the queue

    @property
    def program(self) -> str:
        return program_of(self.module)


@dataclasses.dataclass
class Reading:
    window: tuple  # (start, end) of cb.window, host clock
    chips: int
    executions: list  # every Execution, in window or not
    spans: list  # Spans of the thread holding cb.window, cb.window left out
    steps: dict  # program -> executions starting in the window (summed over chips)
    scope_s: dict  # program -> {scope ("" for none): self seconds, summed over chips}
    unscoped: dict  # program -> {op_name (or <instruction>): self seconds}
    op_s: dict  # program -> {op_name, inherited (or <instruction>): self seconds}
    clock_offset_s: dict  # chip -> offset (chips with a paired execution)
    idle_gaps: list | None = None  # [[label, seconds], ...]: trace_reduce's gaps, causal labels

    @property
    def clock_offset_ms(self):
        if not self.clock_offset_s:
            return None
        return 1e3 * sum(self.clock_offset_s.values()) / len(self.clock_offset_s)

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t < self.window[1]

    def spans_named(self, name: str) -> list:
        """Spans ``name`` that lie in the window."""
        return [s for s in self.spans if s.name == name
                and self.window[0] <= s.start and s.end <= self.window[1]]

    def self_s(self, span: Span, keep=()) -> float:
        """``span``'s time less that of the spans directly inside it, but
        for those named in ``keep``."""
        return span.seconds - sum(self.spans[c].seconds for c in span.children
                                  if self.spans[c].name not in keep)

    def launched_in(self, name: str) -> list:
        """Executions launched in the window inside a span ``name``."""
        return [x for x in self.executions if x.path and name in x.path
                and x.enqueue is not None and self.in_window(x.enqueue)]

    def per_step_ms(self, pattern: str, scopes) -> float | None:
        """Device self time under ``scopes`` per execution of the programs
        matching ``pattern``, per chip, in ms; None where no op of theirs is
        under any of ``scopes``."""
        return self._per_step_ms(pattern, self.scope_s, lambda scope: scope in scopes)

    def per_step_ms_under(self, pattern: str, names) -> float | None:
        """Device self time of the ops whose op_name has a path component in
        ``names`` (any named scope, nested or not, in ``SCOPES`` or not) per
        execution of the programs matching ``pattern``, per chip, in ms; None
        where no op of theirs is under any of ``names``."""
        under = re.compile(r"(?:^|[/(])(?:%s)(?=[)/]|$)" % "|".join(map(re.escape, names)))
        return self._per_step_ms(pattern, self.op_s, under.search)

    def _per_step_ms(self, pattern, table, keep) -> float | None:
        """Self time of the keys of ``table`` (program -> {key: seconds})
        that ``keep`` takes, per execution of the programs matching
        ``pattern``, in ms."""
        n = total = 0.0
        found = False
        for program, by_key in table.items():
            if re.search(pattern, program):
                n += self.steps[program]
                for key, t in by_key.items():
                    if keep(key):
                        found = True
                        total += t
        if not found or n == 0:
            return None
        return 1e3 * total / n

    def scoped_share(self, pattern: str) -> float | None:
        """Share of the matching programs' op self time under some scope."""
        total = scoped = 0.0
        for program, by_scope in self.scope_s.items():
            if re.search(pattern, program):
                total += sum(by_scope.values())
                scoped += sum(v for k, v in by_scope.items() if k)
        return scoped / total if total else None


def program_of(module: str) -> str:
    return re.sub(r"\(\d+\)$", "", module)


def scope_of(op_name: str) -> str:
    """The innermost named scope in an HLO ``op_name`` ("" for none):
    ``jit(f)/transpose(jvp(attention))/dot_general`` -> ``attention``."""
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else ""


# ---------------------------------------------------------------------------
# the trace's events
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Line:
    names: list
    starts: list  # ns
    ends: list  # ns
    stats: list  # dict per event
    parent: list  # index of the innermost event containing it, or -1


def _line(events) -> _Line:
    rows = []
    for ev in events:
        st = dict(getattr(ev, "stats", ()) or ())
        rows.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, st))
    rows.sort(key=lambda r: (r[0], -r[1]))
    line = _Line([r[2] for r in rows], [r[0] for r in rows], [r[1] for r in rows],
                 [r[3] for r in rows], [-1] * len(rows))
    stack: list = []
    for i, (s, e, _, _) in enumerate(rows):
        while stack and line.ends[stack[-1]] <= s:
            stack.pop()
        if stack and e <= line.ends[stack[-1]]:
            line.parent[i] = stack[-1]
        stack.append(i)
    return line


def _is_span(name: str) -> bool:
    return name.startswith(SPAN_PREFIXES) and name != trace_reduce.WINDOW_SPAN


def _self_times(ops):
    """``ops``: [(start, end), ...] of one line -> (each op's self time, the
    index of the op it runs inside or -1), and the indices in start order."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    own = [e - s for s, e in ops]
    parent = [-1] * len(ops)
    stack: list = []
    for i in order:
        s, e = ops[i]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            parent[i] = p = stack[-1]
            own[p] -= min(e, ops[p][1]) - s
        stack.append(i)
    return own, parent, order


def read(planes, op_names: dict | None = None, *, gaps: bool = False) -> Reading:
    """``planes`` as ``trace_reduce.reduce`` takes them, events with ``stats``
    (name, value) where the trace has them; ``op_names``: module event name
    -> {instruction: op_name} (``hlo_op_names``).  With ``gaps`` the reading
    also holds the causal idle-gap labels."""
    op_names = op_names or {}
    devices, host = {}, []
    for plane in planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = {
                ln.name: list(ln.events) for ln in plane.lines
                if ln.name in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE)}
        elif plane.name.startswith("/host:"):
            host.extend(_line(ln.events) for ln in plane.lines)
    window = span_line = None
    for k, line in enumerate(host):
        if trace_reduce.WINDOW_SPAN in line.names:
            i = line.names.index(trace_reduce.WINDOW_SPAN)
            window, span_line = (line.starts[i] * 1e-9, line.ends[i] * 1e-9), k
            break
    if window is None:
        raise ValueError(f"no {trace_reduce.WINDOW_SPAN} span in the trace")
    if not devices:
        raise ValueError("no TPU device plane in the trace")

    spans, index = _spans(host[span_line])
    executions = _executions(devices, host, span_line, spans, index)
    offsets: dict = {}
    for x in executions:
        if x.enqueue is not None:
            offsets[x.chip] = max(offsets.get(x.chip, x.enqueue - x.start), x.enqueue - x.start)
    steps, op_s, unscoped = _scopes(devices, executions, offsets, window, op_names)
    reading = Reading(window=window, chips=len(devices), executions=executions, spans=spans,
                      steps=steps, scope_s=_by_scope(op_s), unscoped=unscoped, op_s=op_s,
                      clock_offset_s=offsets)
    if gaps:
        found = _gaps(devices, window)
        reading.idle_gaps = [[_gap_label(s, e, chip, executions, offsets, spans), e - s]
                             for s, e, chip in found[:trace_reduce.TOP]]
    return reading


def _executions(devices, host, span_line, spans, index) -> list:
    enqueues_by_flow, enqueues_by_run, producer = {}, {}, {}
    for k, line in enumerate(host):
        for i, (name, st) in enumerate(zip(line.names, line.stats)):
            if "_p" in st:
                producer.setdefault(_flow_out(st), (k, i))
            if name == ENQUEUE:
                if "_p" in st:
                    enqueues_by_flow[_flow_out(st)] = (k, i)
                if "run_id" in st:
                    enqueues_by_run[(st["run_id"], st.get("device_ordinal"))] = (k, i)
    out = []
    for chip, lines in devices.items():
        for ev in lines.get(trace_reduce.MODULES_LINE, []):
            st = dict(getattr(ev, "stats", ()) or ())
            at = enqueues_by_flow.get(_flow_in(st)) if "_c" in st else None
            if at is None and "run_id" in st:
                at = enqueues_by_run.get((st["run_id"], chip))
            enqueue = path = None
            span, wait = -1, 0.0
            if at is not None:
                enqueue = host[at[0]].starts[at[1]] * 1e-9
                launch = _launch(host, producer, at, span_line)
                if launch is not None:
                    j, call = launch
                    if call is not None:
                        wait = _queue_wait(host[call[0]], call[1])
                    while j >= 0 and j not in index:
                        j = host[span_line].parent[j]
                    span = index.get(j, -1)
                    path = spans[span].path if span >= 0 else ()
                    s = span
                    while s >= 0:
                        spans[s].queue_wait += wait
                        s = spans[s].parent
            out.append(Execution(chip, ev.name, ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9, enqueue, path, span,
                                 wait))
    return out


def _flow_out(stats):
    """A flow leaving an event: (its type, its id); ids of different types
    may be equal."""
    return stats.get("_pt"), stats["_p"]


def _flow_in(stats):
    return stats.get("_ct"), stats["_c"]


def _launch(host, producer, at, span_line):
    """Follow flow ids from an event up to the Python thread -> (the index
    of the event reached there, (line, index) of the event whose flow led
    there, the runtime's launch call, or None), or None."""
    k, i = at
    call = None
    seen = set()
    for _ in range(MAX_HOPS):
        line = host[k]
        if k == span_line:
            return i, call
        j = i
        while j >= 0:
            st = line.stats[j]
            hop = producer.get(_flow_in(st)) if "_c" in st else None
            if hop is not None and hop not in seen:
                break
            j = line.parent[j]
        else:
            return None
        call = (k, j)
        seen.add(hop)
        k, i = hop
    return None


def _queue_wait(line: _Line, j: int) -> float:
    """Self time, in seconds, of the ``QUEUE_WAIT`` events inside event
    ``j`` of ``line``."""
    waits: dict = {}
    i = j + 1
    while i < len(line.names) and line.starts[i] < line.ends[j]:
        if line.names[i] == QUEUE_WAIT:
            waits[i] = line.ends[i] - line.starts[i]
        elif line.parent[i] in waits:
            waits[line.parent[i]] -= line.ends[i] - line.starts[i]
        i += 1
    return sum(waits.values()) * 1e-9


def _spans(line: _Line):
    """-> (spans, {line event index: index in spans})"""
    out, index = [], {}
    for i, name in enumerate(line.names):
        if not _is_span(name):
            continue
        j = line.parent[i]
        while j >= 0 and j not in index:
            j = line.parent[j]
        parent = index.get(j, -1)
        path = (out[parent].path if parent >= 0 else ()) + (name,)
        if parent >= 0:
            out[parent].children.append(len(out))
        index[i] = len(out)
        args = {k: v for k, v in line.stats[i].items() if not k.startswith("_")}
        out.append(Span(name, line.starts[i] * 1e-9, line.ends[i] * 1e-9, path, args,
                        parent, []))
    return out, index


def _gaps(devices, window) -> list:
    """trace_reduce's idle gaps, in its order, each with its chip."""
    lo, hi = window
    gaps = []
    for chip, dev in devices.items():
        ops = [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
               for ev in dev.get(trace_reduce.OPS_LINE, [])]
        ops = [(s, e) for s, e in ops if e > lo and s < hi]
        merged = trace_reduce.union(trace_reduce.clip(ops, lo, hi))
        gaps.extend((s, e, chip) for s, e in trace_reduce.subtract([[lo, hi]], merged))
    gaps.sort(key=lambda g: g[0] - g[1])
    return gaps


def _cover(spans, a: float, b: float) -> str:
    """The span path that held the host for longest in [a, b): each span's
    overlap less its children's."""
    own = {i: min(s.end, b) - max(s.start, a) for i, s in enumerate(spans)
           if s.start < b and s.end > a}
    for i in list(own):
        if spans[i].parent in own:
            own[spans[i].parent] -= own[i]
    held: dict = {}
    for i, t in own.items():
        held[spans[i].path] = held.get(spans[i].path, 0.0) + t
    if not held:
        return NONE
    best = max(held, key=lambda p: (held[p], len(p)))
    return ">".join(best) if held[best] > 0 else NONE


def _gap_label(s, e, chip, executions, offsets, spans) -> str:
    off = offsets.get(chip, 0.0)
    ending = [x for x in executions if x.chip == chip and x.end > e]
    x = min(ending, key=lambda x: x.start) if ending else None
    if x is None or x.enqueue is None:
        return _cover(spans, s + off, e + off)
    if x.enqueue > s + off:
        return _cover(spans, s + off, x.enqueue)
    return QUEUED


def _scopes(devices, executions, offsets, window, op_names):
    """Op self time of the executions that start in the window, by program
    and op_name (inherited), and of those under no scope by their own."""
    steps, op_s, unscoped = {}, {}, {}
    for chip, dev in devices.items():
        off = offsets.get(chip, 0.0)
        mods = sorted((x for x in executions if x.chip == chip), key=lambda x: x.start)
        starts = [x.start for x in mods]
        for x in mods:
            if window[0] <= x.start + off < window[1]:
                steps[x.program] = steps.get(x.program, 0) + 1
        events = dev.get(trace_reduce.OPS_LINE, [])
        ops = [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9) for ev in events]
        own_s, parent, order = _self_times(ops)
        op_at = [""] * len(ops)  # parents come first in start order
        names: dict = {}
        for i in order:
            ev, s = events[i], ops[i][0]
            k = bisect.bisect_right(starts, s) - 1
            if k < 0 or s >= mods[k].end:
                continue
            x = mods[k]
            instr = names.get(ev.name)
            if instr is None:
                instr = names[ev.name] = trace_reduce.op_name(ev.name)
            op = op_names.get(x.module, {}).get(instr, "")
            # an op the compiler added (no op_name) inside a loop or call
            # takes the op_name, and so the scope, of the op it runs in
            named = op_at[i] = op if op or parent[i] < 0 else op_at[parent[i]]
            if not window[0] <= x.start + off < window[1]:
                continue
            own = own_s[i]
            by_op = op_s.setdefault(x.program, {})
            key = named or f"<{instr}>"
            by_op[key] = by_op.get(key, 0.0) + own
            if not _scope_of_key(key):
                u = unscoped.setdefault(x.program, {})
                key = op or f"<{instr}>"
                u[key] = u.get(key, 0.0) + own
    return steps, op_s, unscoped


@functools.lru_cache(maxsize=None)
def _scope_of_key(key: str) -> str:
    """The scope of an ``op_s`` key ("" for an ``<instruction>``)."""
    return "" if key.startswith("<") else scope_of(key)


def _by_scope(op_s: dict) -> dict:
    """``op_s`` summed by scope: program -> {scope ("" for none): seconds}."""
    scope_s: dict = {}
    for program, by_op in op_s.items():
        by = scope_s[program] = {}
        for op, t in by_op.items():
            scope = _scope_of_key(op)
            by[scope] = by.get(scope, 0.0) + t
    return scope_s


# ---------------------------------------------------------------------------
# the HLO the trace carries
# ---------------------------------------------------------------------------


def _varint(b, i):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b, lo, hi):
    """Protocol buffer wire format: (field, wire type, value) of b[lo:hi];
    a length-delimited value is its (start, end)."""
    i = lo
    while i < hi:
        key, i = _varint(b, i)
        f, w = key >> 3, key & 7
        if w == 0:
            v, i = _varint(b, i)
        elif w == 1:
            v, i = b[i:i + 8], i + 8
        elif w == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif w == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {w} at byte {i}")
        yield f, w, v


def _sub(b, at, field):
    return [v for f, _, v in _fields(b, *at) if f == field]


def _text(b, at) -> str:
    return bytes(b[at[0]:at[1]]).decode("utf-8", "replace")


def _ints(b, f_w_v) -> list:
    out = []
    for w, v in f_w_v:
        if w == 0:
            out.append(v)
        else:  # packed
            i, hi = v
            while i < hi:
                x, i = _varint(b, i)
                out.append(x)
    return out


def _module_instructions(b, module):
    """HloModuleProto -> ({computation id: [instruction id, ...]},
    {instruction id: (name, op_name, operand ids, called computation ids)})."""
    comps, instr = {}, {}
    for comp in _sub(b, module, 3):  # computations
        ids = comps[(_sub(b, comp, 5) or [None])[0]] = []
        for ins in _sub(b, comp, 2):  # instructions
            name = op = ""
            iid, operands, called = None, [], []
            for f, w, v in _fields(b, *ins):
                if f == 1:
                    name = _text(b, v)
                elif f == 7:  # OpMetadata
                    op = next((_text(b, x) for x in _sub(b, v, 2)), "")
                elif f == 35:
                    iid = v
                elif f == 36:  # operand_ids
                    operands.append((w, v))
                elif f == 38:  # called_computation_ids
                    called.append((w, v))
            ids.append(iid)
            instr[iid] = (name, op, _ints(b, operands), _ints(b, called))
    return comps, instr


def _module_op_names(b, module) -> dict:
    """HloModuleProto -> {instruction: op_name}.  An instruction the compiler
    added has no op_name of its own: it takes the first found in the
    computations it calls, else along its operands (the value it works on)."""
    comps, instr = _module_instructions(b, module)

    @functools.lru_cache(maxsize=None)
    def resolve(iid, depth=0) -> str:
        _, op, operands, called = instr[iid]
        if op or depth == MAX_HOPS:
            return op
        nxt = [i for c in called for i in comps.get(c, ())] + operands
        return next((found for i in nxt if i in instr and (found := resolve(i, depth + 1))), "")

    return {fields[0]: resolve(iid) for iid, fields in instr.items()}


def hlo_op_names(data: bytes) -> dict:
    """A serialized XSpace -> {module event name: {instruction: op_name}},
    from the HLO each program's metadata carries."""
    b = memoryview(data)
    out = {}
    for plane in _sub(b, (0, len(b)), 1):
        if METADATA_PLANE not in _text(b, next(iter(_sub(b, plane, 2)), (0, 0))):
            continue
        stat_ids = set()
        for entry in _sub(b, plane, 5):  # stat_metadata
            for md in _sub(b, entry, 2):
                if any(_text(b, n) == HLO_STAT for n in _sub(b, md, 2)):
                    stat_ids.update(_sub(b, md, 1))
        for entry in _sub(b, plane, 4):  # event_metadata
            for md in _sub(b, entry, 2):
                name = next((_text(b, n) for n in _sub(b, md, 2)), "")
                for stat in _sub(b, md, 5):
                    if not set(_sub(b, stat, 1)) & stat_ids:
                        continue
                    for proto in _sub(b, stat, 6):
                        for module in _sub(b, proto, 1):
                            out[name] = _module_op_names(b, module)
    return out


def read_file(path: str, *, gaps: bool = True) -> Reading:
    """The reading of a trace file; by default with the clock offsets and
    causal gap labels, for an operator."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        data = f.read()
    return read(ProfileData.from_serialized_xspace(data).planes, hlo_op_names(data), gaps=gaps)


@functools.lru_cache(maxsize=2)
def _read_once(path: str, mtime_ns: int, size: int) -> Reading:
    return read_file(path, gaps=False)


def newest_trace() -> str | None:
    """The newest ``.xplane.pb`` under the benchmark's trace directories."""
    paths = sorted((common.OUT / "trace").glob("*/plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return str(paths[-1]) if paths else None


def of(reduced) -> Reading | None:
    """The reading of the trace ``reduced`` was reduced from: the newest
    trace of the benchmark, if its window is that of ``reduced``; None
    where there is no such trace.  A trace that cannot be read raises."""
    path = newest_trace()
    if path is None:
        return None
    st = os.stat(path)
    reading = _read_once(path, st.st_mtime_ns, st.st_size)
    if abs((reading.window[1] - reading.window[0]) - reduced.window_s) > 1e-9:
        return None
    return reading
