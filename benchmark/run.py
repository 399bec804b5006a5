#!/usr/bin/env python3
"""One run of one cell of the benchmark, in one process.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration is ``benchmark/configs/<config>.json``, its traffic
``benchmark/workloads/<traffic>.json``, the traffic's ``driver`` is
``benchmark/drivers/<driver>.py`` and each per-layer metric is read by
``benchmark/layer_metrics/<metric>.py``: all found by name, so a later
PR adds files and entries and edits none (benchmark/README.md).

The last line of standard output is the result: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and, traced,
``breakdown``).  Without a TPU the run refuses, unless ``--rehearse``
is given: a rehearsal runs the same code at the files' ``rehearse``
sizes on whatever jax has, names no device, and reports no number that
only a device trace can give.
"""
import argparse
import importlib.util
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()         # set-up is counted from here

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """``benchmark/<kind>/<name>.py`` as a module, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit("benchmark: no %s named %r (%s)" % (kind, name, path))
    spec = importlib.util.spec_from_file_location(
        "benchmark_%s_%s" % (kind, name.replace(".", "_")), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def merge(base, over):
    """``over`` laid on ``base``, dicts merged key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


class Cell:
    """What one run is given: the cell, its files, the arguments."""

    def __init__(self, bench, args):
        self.bench = bench
        rows = [w for w in bench["workloads"] if w["name"] == args.workload]
        if not rows:
            raise SystemExit("benchmark: no cell %r in BENCHMARK.json (has: %s)"
                             % (args.workload,
                                [w["name"] for w in bench["workloads"]]))
        self.entry = rows[0]
        self.name = self.entry["name"]
        self.chips = int(self.entry["chips"])
        cfg_row = [c for c in bench["configs"]
                   if c["name"] == self.entry["config"]][0]
        self.config = load_json(ROOT, cfg_row["file"])
        self.traffic = load_json(HERE, "workloads",
                                 self.entry["traffic"] + ".json")
        self.rehearse = bool(args.rehearse)
        if self.rehearse:
            self.config = merge(self.config, self.config.get("rehearse", {}))
            self.traffic = merge(self.traffic,
                                 self.traffic.get("rehearse", {}))
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.scratch = os.path.join(ROOT, ".bench_scratch", self.name)
        self.t_process = T_PROCESS

    def metrics(self, group):
        """Declared metrics of ``group`` that this cell reports."""
        return [m for m in self.bench[group]
                if self.name in m.get("workloads", [self.name])]


def prepare():
    """The compile cache where JAX_COMPILATION_CACHE_DIR says, else one
    fixed path in the checkout (the path is part of the cache's key);
    the repo and the benchmark importable."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    for p in (ROOT, HERE):
        if p not in sys.path:
            sys.path.insert(0, p)


def device_info(cell):
    """jax's devices, checked against what the cell asks for."""
    import jax
    devs = jax.devices()
    if cell.rehearse:
        return {"platform": devs[0].platform, "kind": "rehearsal",
                "count": len(devs), "rehearsal": True}
    if devs[0].platform != "tpu":
        raise SystemExit("benchmark: jax found no TPU (platform %r); a "
                         "measurement never falls back to the CPU "
                         "(--rehearse runs the code without measuring)"
                         % devs[0].platform)
    if len(devs) < cell.chips:
        raise SystemExit("benchmark: cell %s needs %d chip(s), jax reports %d"
                         % (cell.name, cell.chips, len(devs)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes():
    """The most the fullest chip held at once, from jax's own statistics:
    the peak of the live buffers, or the live buffers now plus the
    largest scratch region a compiled program reserved, whichever is
    larger.  (jax keeps a step's scratch under ``peak_bytes_reserved``,
    outside ``peak_bytes_in_use``: for a convolutional step that is most
    of what the chip holds.)"""
    import jax
    peak = 0
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)),
                   int(st.get("bytes_in_use", 0))
                   + int(st.get("peak_bytes_reserved", 0)))
    return peak


def layer_metrics(cell, facts):
    """Each declared per-layer metric of the cell, from its reader."""
    out = {}
    for m in cell.metrics("per_layer"):
        if cell.rehearse and m["source"] == "device_trace":
            out[m["name"]] = {"value": None, "unit": m["unit"]}
            continue
        value = load_module("layer_metrics", m["name"]).read(facts)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def execute(cell, device):
    """Drive the cell's driver and build the result line (a dict)."""
    driver = load_module("drivers", cell.traffic["driver"])
    res = driver.run(cell)
    checks = res["checks"]
    for c in checks:
        print("check %-34s %-12.6g limit %-10s %s" % (
            c["name"], c["value"], c["limit"], "ok" if c["ok"] else "FAILED"))
    line = {"correct": all(c["ok"] for c in checks),
            "attempted": int(res["attempted"]), "failed": int(res["failed"])}
    tr = res["facts"].get("trace") if cell.trace else None
    if cell.trace:
        line["metrics"] = layer_metrics(cell, res["facts"])
        if tr:
            device = dict(device, busy_s=tr["busy_s"],
                          window_s=tr["window_s"])
            line["breakdown"] = {"device_ops": tr["device_ops"],
                                 "idle_gaps": tr["idle_gaps"]}
    else:
        line["metrics"] = {
            m["name"]: {"value": float(res["end_to_end"][m["name"]]),
                        "unit": m["unit"]}
            for m in cell.metrics("end_to_end")}
    line["device"] = dict(device, memory_peak_bytes=memory_peak_bytes())
    line.update(cell=cell.name, seed=cell.seed, notes=res.get("notes", {}))
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run the code at the files' rehearse sizes on "
                         "whatever jax has; measures nothing")
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    if args.seconds is None:
        args.seconds = bench["run_seconds"]
    cell = Cell(bench, args)
    prepare()
    line = execute(cell, device_info(cell))
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
