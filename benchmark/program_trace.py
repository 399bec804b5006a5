"""The program's own names in a ``jax.profiler`` trace: host spans of
``mxnet_tpu.telemetry.tracing`` (``fit.prepare``, ``metric.readback``,
...) and device time by the ``jax.named_scope`` names the program puts
into every compiled step (an operator's class and node name,
``fit.update``, ``pallas.<kernel>``), plus the programs the chip ran
(the ``XLA Modules`` line).

The harness's ``facts`` carry no trace path, so :func:`current` finds
this process's trace itself: the newest ``*.xplane.pb`` under
``<root>/.bench_scratch/*/trace`` that is not older than the process,
read once per process and served to every reader under
``layer_metrics/`` that asks.

Host spans come through ``jax.profiler.ProfileData``.  A device event's
scope path is a stat of its *metadata* (``tf_op``, beside
``hlo_category``, ``flops`` and ``bytes_accessed``), which
``ProfileData`` does not expose, so the device planes are read from the
``XSpace`` protobuf's wire format by the small reader below (nothing but
the standard library; tsl/profiler/protobuf/xplane.proto gives the
field numbers).

The arithmetic works on plain tuples and has hand-worked tests
(tests/benchmark/test_program_trace.py).  Time under a scope is the
UNION of its events' intervals (``trace_reduce.busy_ns``), never a sum:
a ``cond`` and the instructions inside it may both be on the line.

    python3 benchmark/program_trace.py [trace.xplane.pb]

prints the table PERF.md section 5 keeps: device ms per step by scope.
"""
import functools
import glob
import json
import os
import re
import struct
import sys

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

MODULES_LINE = "XLA Modules"
WINDOW = "bench_window"
# the host spans the readers ask for (mxnet_tpu/telemetry/tracing.py
# sites on the training path), and the window the benchmark opens
HOST_SPANS = ("fit.prepare", "fit.fused_dispatch", "fit.rebind",
              "metric.readback")
# jax writes a scope traced under a transformation as jvp(<scope>) or
# transpose(jvp(<scope>)): forward and backward of one operator
_WRAPPED = re.compile(r"^((?:(?:transpose|jvp|vmap)\()*)([^()]*)\)*$")


# ----------------------------------------------------------------------
# the XSpace wire format: just enough to reach an event's metadata
# ----------------------------------------------------------------------
def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf, start, end):
    """``(field number, value)`` of each field of the message in
    ``buf[start:end]``.  A varint comes as an int, a length-delimited
    field as its ``(start, end)`` in ``buf``, a fixed field as bytes."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire == 1:
            value = buf[i:i + 8]
            i += 8
        elif wire == 5:
            value = buf[i:i + 4]
            i += 4
        else:
            raise ValueError("XSpace: wire type %d at byte %d" % (wire, i))
        yield key >> 3, value


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _stat(buf, span, stat_names):
    """``(name, value)`` of one XStat; a ``ref_value`` is the name of
    the stat metadata it points at."""
    name, value = None, None
    for f, v in fields(buf, *span):
        if f == 1:
            name = stat_names.get(v)
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f in (3, 4):
            value = v - (1 << 64) if f == 4 and v >> 63 else v
        elif f == 5:
            value = _text(buf, v)
        elif f == 7:
            value = stat_names.get(v)
    return name, value


def _event_metadata(buf, span, stat_names):
    """One XEventMetadata as a dict: ``name``, ``display_name`` and its
    stats by name."""
    out = {"name": "", "display_name": ""}
    for f, v in fields(buf, *span):
        if f == 2:
            out["name"] = _text(buf, v)
        elif f == 4:
            out["display_name"] = _text(buf, v)
        elif f == 5:
            k, value = _stat(buf, v, stat_names)
            if k is not None:
                out[k] = value
    return out


def _line(buf, span, wanted=None):
    """``(name, timestamp_ns, [(metadata id, offset_ps, duration_ps)])``
    of one XLine.  With ``wanted``, the events of a line whose name is
    not in it are not decoded; no event's own stats ever are."""
    name, ts, event_spans = "", 0, []
    for f, v in fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            ts = v
        elif f == 4:
            event_spans.append(v)
    events = []
    for v in event_spans if wanted is None or name in wanted else ():
        mid = off = dur = 0
        for g, w in fields(buf, *v):
            if g == 1:
                mid = w
            elif g == 2:
                off = w
            elif g == 3:
                dur = w
        events.append((mid, off, dur))
    return name, ts, events


def read_device_planes(path, lines=(trace_reduce.OPS_LINE, MODULES_LINE)):
    """``{plane name: {line name: [event, ...]}}`` for the device planes
    of an ``*.xplane.pb``.  An event is a dict: ``name`` (the
    instruction's or the program's name), ``start_ns``, ``dur_ns`` and,
    from its metadata, ``tf_op`` (jax's ``op_name`` path, where
    ``jax.named_scope`` names land), ``category``, ``flops``,
    ``bytes_accessed`` (None where the metadata has none)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes = {}
    for f, plane in fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, line_spans, meta_spans, stat_names = "", [], [], {}
        for g, v in fields(buf, *plane):
            if g == 2:
                name = _text(buf, v)
            elif g == 3:
                line_spans.append(v)
            elif g == 4:
                meta_spans.append(v)
            elif g == 5:        # map entry: key 1, XStatMetadata 2
                entry = dict(fields(buf, *v))
                sm = dict(fields(buf, *entry[2])) if 2 in entry else {}
                stat_names[entry.get(1, 0)] = _text(buf, sm[2]) \
                    if 2 in sm else ""
        if not name.startswith(trace_reduce.DEVICE_PLANE):
            continue
        metadata = {}
        for v in meta_spans:    # map entry: key 1, XEventMetadata 2
            entry = dict(fields(buf, *v))
            if 2 in entry:
                metadata[entry.get(1, 0)] = _event_metadata(
                    buf, entry[2], stat_names)
        out = {}
        for v in line_spans:
            line_name, ts, events = _line(buf, v, lines)
            if line_name not in lines:
                continue
            rows = out.setdefault(line_name, [])
            for mid, off, dur in events:
                md = metadata.get(mid, {})
                rows.append({
                    "name": trace_reduce.op_name(
                        md.get("display_name") or md.get("name", "")),
                    "start_ns": ts + off / 1e3, "dur_ns": dur / 1e3,
                    "tf_op": (md.get("tf_op") or "").rsplit(":", 1)[0],
                    "category": md.get("hlo_category"),
                    "flops": md.get("flops"),
                    "bytes_accessed": md.get("bytes_accessed")})
            rows.sort(key=lambda e: e["start_ns"])
        planes[name] = out
    return planes


# ----------------------------------------------------------------------
# scopes: which of the program's names an instruction carries
# ----------------------------------------------------------------------
@functools.lru_cache(maxsize=1 << 16)
def scopes_of(tf_op, op_classes):
    """The program's scopes in one ``tf_op`` path, outermost first:
    ``(label, direction)`` with label ``fit.<region>``,
    ``pallas.<kernel>`` or ``op.<operator class>``.  Direction is
    ``"bwd"`` from the first element jax wrote under ``transpose(...)``
    inwards (a kernel inside an operator's backward pass is backward),
    else ``"fwd"``.  ``op_classes`` is the set of operator names the
    program registers (a frozenset: results are remembered by path);
    the path's last element is the primitive, never a scope
    (``.../transpose/transpose0/transpose`` holds one operator)."""
    out, direction = [], "fwd"
    for part in tf_op.split("/")[:-1]:
        m = _WRAPPED.match(part)
        if not m:
            continue
        wrap, word = m.groups()
        if "transpose(" in wrap:
            direction = "bwd"
        if word.startswith(("fit.", "pallas.")):
            out.append((word, direction))
        elif word in op_classes:
            out.append(("op." + word, direction))
    return tuple(out)


def intervals(events):
    """Events (dicts) as ``trace_reduce``'s ``(name, start, dur)``."""
    return [(e["name"], e["start_ns"], e["dur_ns"]) for e in events]


def group_by_scope(events, op_classes, kinds=("fit.", "pallas.", "op.")):
    """``{(label, direction): [event, ...]}``: each event under the
    OUTERMOST of its scopes whose label starts with one of ``kinds``
    (an operator inside ``fit.update`` goes under ``fit.update``; a
    kernel inside an operator goes under the operator unless ``kinds``
    asks for ``pallas.`` alone)."""
    groups = {}
    for e in events:
        for label, direction in scopes_of(e["tf_op"], op_classes):
            if label.startswith(tuple(kinds)):
                groups.setdefault((label, direction), []).append(e)
                break
    return groups


def time_by_scope(events, op_classes, kinds=("fit.", "pallas.", "op.")):
    """``{(label, direction): ns}``: the union of the intervals of each
    of :func:`group_by_scope`'s groups."""
    return {k: trace_reduce.busy_ns(intervals(v))
            for k, v in group_by_scope(events, op_classes, kinds).items()}


def scoped_ns(events, op_classes, prefix=""):
    """Union of the intervals of the events that carry some scope
    whose label starts with ``prefix`` (any scope by default)."""
    return trace_reduce.busy_ns(intervals(
        [e for e in events
         if any(label.startswith(prefix)
                for label, _ in scopes_of(e["tf_op"], op_classes))]))


def idle_inside(events, spans, t0, t1):
    """ns of [t0, t1] in which no event ran AND one of ``spans``
    (``(name, start, dur)``) was open."""
    open_ = trace_reduce.merged(spans)
    total = 0.0
    for a, b in trace_reduce.gaps(intervals(events), t0, t1):
        total += sum(max(0.0, min(b, s1) - max(a, s0)) for s0, s1 in open_)
    return total


# ----------------------------------------------------------------------
# this process's trace
# ----------------------------------------------------------------------
def process_start_s():
    """The wall-clock second this process started (Linux's /proc); 0.0
    where that cannot be read, which admits every trace."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            boot = next(int(l.split()[1]) for l in f
                        if l.startswith("btime"))
        return boot + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration, IndexError):
        return 0.0


def find_trace(root=ROOT, not_before=None):
    """The newest ``*.xplane.pb`` under ``<root>/.bench_scratch/*/trace``
    written since ``not_before`` (this process's start by default);
    None when there is none."""
    if not_before is None:
        not_before = process_start_s()
    paths = glob.glob(os.path.join(root, ".bench_scratch", "*", "trace",
                                   "**", "*.xplane.pb"), recursive=True)
    # a second's slack: the process's start is known to a clock tick
    stamped = [(os.path.getmtime(p), p) for p in paths]
    stamped = [sp for sp in stamped if sp[0] >= not_before - 1.0]
    return max(stamped)[1] if stamped else None


def program_op_classes():
    """The operator names the program registers (the first element of
    every node's scope)."""
    from mxnet_tpu.ops import registry
    return frozenset(registry.list_ops())


class Trace:
    """One trace, reduced to what the readers ask: the steady window,
    the program's host spans in it, and the first device's operations
    and programs in it."""

    def __init__(self, path, op_classes=None):
        self.path = path
        self.op_classes = program_op_classes() if op_classes is None \
            else frozenset(op_classes)
        host = trace_reduce.read_events(
            path, HOST_SPANS + (WINDOW,))["host"]
        win = [h for h in host if h[0] == WINDOW]
        planes = read_device_planes(path)
        first = planes[sorted(planes)[0]] if planes else {}
        ops = first.get(trace_reduce.OPS_LINE, [])
        modules = first.get(MODULES_LINE, [])
        if win:
            self.t0, self.t1 = win[0][1], win[0][1] + win[0][2]
        elif ops:
            self.t0 = ops[0]["start_ns"]
            self.t1 = max(e["start_ns"] + e["dur_ns"] for e in ops)
        else:
            self.t0 = self.t1 = 0.0
        self.spans = [h for h in trace_reduce.clip(host, self.t0, self.t1)
                      if h[0] != WINDOW]
        self.ops = self._inside(ops)
        self.modules = self._inside(modules)

    def _inside(self, events):
        out = []
        for e in events:
            a = max(e["start_ns"], self.t0)
            b = min(e["start_ns"] + e["dur_ns"], self.t1)
            if b > a:
                out.append(dict(e, start_ns=a, dur_ns=b - a))
        return out

    def span_ns(self, name):
        """Summed duration of the host spans called ``name``; None
        where the window holds none (a program without the span)."""
        durs = [d for n, _, d in self.spans if n == name]
        return sum(durs) if durs else None

    def span_count(self, name):
        return sum(1 for n, _, _ in self.spans if n == name)

    def has_scopes(self):
        """Whether the program wrote its scope names at all: a program
        from before them reads as nothing, not as 0."""
        return scoped_ns(self.ops, self.op_classes) > 0

    def scope_ns(self, prefix):
        return scoped_ns(self.ops, self.op_classes, prefix)

    def busy_ns(self):
        return trace_reduce.busy_ns(intervals(self.ops))

    def idle_inside_ns(self, name):
        """Device idle time inside the host spans called ``name``; None
        without device operations or without the span."""
        spans = [s for s in self.spans if s[0] == name]
        if not self.ops or not spans:
            return None
        return idle_inside(self.ops, spans, self.t0, self.t1)

    def table(self, steps):
        """Device ms per step by scope: ``{"fit": ..., "pallas": ...,
        "op": ...}`` rows of ``[label, direction, ms, GB/s]``, longest
        first; GB/s from the compiler's ``bytes_accessed`` summed over
        the scope's events (not checked for ``cond`` and Pallas custom
        calls: PERF.md section 7)."""
        out = {}
        for kind in ("fit.", "pallas.", "op."):
            rows = []
            for (label, direction), evs in group_by_scope(
                    self.ops, self.op_classes, (kind,)).items():
                ns = trace_reduce.busy_ns(intervals(evs))
                moved = sum(e["bytes_accessed"] or 0 for e in evs
                            if e["category"] not in ("conditional",
                                                     "while"))
                rows.append([label, direction, ns / 1e6 / steps,
                             moved / ns if ns else 0.0])
            out[kind.rstrip(".")] = sorted(rows, key=lambda r: -r[2])
        return out


_CACHE = {}


def current():
    """This process's :class:`Trace`, read once; None where there is no
    trace to read (an untraced run)."""
    path = find_trace()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = Trace(path)
    return _CACHE[key]


def train_trace(facts):
    """:func:`current` for a training cell's ``facts``, else None."""
    return current() if facts.get("kind") == "train" else None


def per_step(facts, ns):
    """``ns`` of the window as milliseconds per step of it."""
    if ns is None or not facts.get("steps"):
        return None
    return ns / 1e6 / facts["steps"]


def main(argv):
    path = argv[1] if len(argv) > 1 else find_trace(not_before=0.0)
    if path is None:
        raise SystemExit("program_trace: no trace under %s"
                         % os.path.join(ROOT, ".bench_scratch"))
    for p in (ROOT, HERE):
        if p not in sys.path:
            sys.path.insert(0, p)
    tr = Trace(path)
    steps = tr.span_count("fit.fused_dispatch") or 1
    idle = (tr.t1 - tr.t0) - tr.busy_ns()
    print(json.dumps({
        "trace": path, "steps": steps,
        "window_ms": (tr.t1 - tr.t0) / 1e6,
        "busy_ms_per_step": tr.busy_ns() / 1e6 / steps,
        "idle_ms_per_step": idle / 1e6 / steps,
        "scoped_ms_per_step": tr.scope_ns("") / 1e6 / steps,
        "host_span_ms_per_step": {
            n: (tr.span_ns(n) or 0.0) / 1e6 / steps for n in HOST_SPANS},
        "idle_inside_ms_per_step": {
            n: (tr.idle_inside_ns(n) or 0.0) / 1e6 / steps
            for n in HOST_SPANS},
        "programs": sorted({e["name"] for e in tr.modules}),
        "programs_per_step": len(tr.modules) / steps,
        "device_ms_per_step": tr.table(steps)}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
