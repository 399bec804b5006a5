"""From a ``jax.profiler`` trace (``*.xplane.pb``) to the numbers the
benchmark reports: device busy time, idle share, the device operations
that took most time, and the longest idle gaps labelled by the
benchmark's own host span they fall in.

Reading needs nothing but jax (``jax.profiler.ProfileData``).  The
arithmetic works on plain ``(name, start_ns, duration_ns)`` tuples, so
each function has a hand-worked test (tests/benchmark).

Device events are those of the device planes' ``XLA Ops`` line: one
event per executed HLO operation (a fusion, a custom call, a copy), by
the names the compiler gave them.  Host spans are the benchmark's own
``jax.profiler.TraceAnnotation`` names, found on the host plane; the
profiler puts both on one clock.
"""
import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError("no *.xplane.pb under %s" % trace_dir)
    return paths[-1]


def read_events(path, host_names=()):
    """``{"devices": {plane name: [(name, start_ns, dur_ns), ...]},
    "host": [(name, start_ns, dur_ns), ...]}``.  ``host`` holds only
    events whose name is in ``host_names``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    want = set(host_names)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs += [(op_name(e.name), float(e.start_ns),
                             float(e.duration_ns)) for e in line.events]
            devices[plane.name] = sorted(evs, key=lambda e: e[1])
        elif plane.name == HOST_PLANE and want:
            for line in plane.lines:
                host += [(e.name, float(e.start_ns), float(e.duration_ns))
                         for e in line.events if e.name in want]
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host}


def op_name(text):
    """The instruction's name from the trace's event text: ``%fusion.3 =
    bf16[...] fusion(...)`` -> ``fusion.3``."""
    return text.split(" =", 1)[0].strip().lstrip("%")


def clip(events, t0, t1):
    """Events cut to the window [t0, t1] (ns)."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def merged(events):
    """Sorted, non-overlapping [start, end] intervals covering every
    event."""
    out = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return out


def busy_ns(events):
    """Length of the union of the events' intervals."""
    return sum(b - a for a, b in merged(events))


def idle_share(events, t0, t1):
    """1 - busy / window over [t0, t1], as a fraction."""
    if t1 <= t0:
        raise ValueError("empty window")
    return 1.0 - busy_ns(clip(events, t0, t1)) / (t1 - t0)


def op_sums(events, top=10):
    """[(name, total_ns)] of the ``top`` instructions by summed
    duration (all of them when ``top`` is None), longest first."""
    tot = {}
    for name, _, d in events:
        tot[name] = tot.get(name, 0.0) + d
    rows = sorted(tot.items(), key=lambda kv: (-kv[1], kv[0]))
    return rows if top is None else rows[:top]


def gaps(events, t0, t1):
    """[(start_ns, end_ns)] idle intervals of [t0, t1], longest first."""
    out, at = [], t0
    for a, b in merged(clip(events, t0, t1)):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if t1 > at:
        out.append((at, t1))
    return sorted(out, key=lambda g: (g[0] - g[1], g[0]))


def attribute_gaps(gap_list, host_spans, top=10, other="unattributed"):
    """[(span name, idle_ns)]: each gap's time is given to the host
    spans it overlaps, by overlap; what no span covers goes to
    ``other``.  Longest total first, at most ``top`` rows."""
    tot = {}
    for a, b in gap_list:
        covered = 0.0
        for name, s, d in host_spans:
            o = min(b, s + d) - max(a, s)
            if o > 0:
                tot[name] = tot.get(name, 0.0) + o
                covered += o
        rest = (b - a) - covered
        if rest > 0:
            tot[other] = tot.get(other, 0.0) + rest
    return sorted(tot.items(), key=lambda kv: (-kv[1], kv[0]))[:top]


def summarize(path, host_names, window_name=None):
    """Everything the harness needs from one trace.

    The steady window is the span named ``window_name`` on the host
    plane when given (the benchmark opens one around the traced part of
    its loop), else first device event to last.  Busy time is averaged
    over the device planes; operations and gaps are the first device's.
    """
    ev = read_events(path, set(host_names) | ({window_name} - {None}))
    if not ev["devices"] or not any(ev["devices"].values()):
        raise RuntimeError("the trace holds no device operation "
                           "(planes with an %r line: none)" % OPS_LINE)
    names = sorted(ev["devices"])
    first = ev["devices"][names[0]]
    win = [h for h in ev["host"] if h[0] == window_name]
    if win:
        t0, t1 = win[0][1], win[0][1] + win[0][2]
    else:
        allev = [e for n in names for e in ev["devices"][n]]
        t0 = min(e[1] for e in allev)
        t1 = max(e[1] + e[2] for e in allev)
    busy = [busy_ns(clip(ev["devices"][n], t0, t1)) for n in names]
    spans = [h for h in clip(ev["host"], t0, t1) if h[0] != window_name]
    inwin = clip(first, t0, t1)
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "idle_share": 1.0 - sum(busy) / len(busy) / (t1 - t0),
        "device_ops": [[n, d / 1e9] for n, d in op_sums(inwin, 10)],
        "idle_gaps": [[n, d / 1e9] for n, d in attribute_gaps(
            gaps(inwin, t0, t1), spans, 10)],
        "n_device_events": len(inwin),
        "host_span_s": {n: sum(d for m, _, d in spans if m == n) / 1e9
                        for n in host_names},
        "host_span_n": {n: sum(1 for m, _, _ in spans if m == n)
                        for n in host_names},
    }
