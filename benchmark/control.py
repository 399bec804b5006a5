#!/usr/bin/env python3
"""The control of a cell's ``correct``: the plain reference put in the
program's place and computed in the nearest precision below the one the
configuration states (fp8 operands for bfloat16), read against the
float32 reference with the run's own comparison.  It has to come out
as NOT correct.  Run on the chip at the cell's own size when a limit is
set (PERF.md gives the readings); tests/benchmark keeps it at a size a
test run can hold.

    python benchmark/control.py --workload <cell> --seeds 1,2,3
"""
import argparse
import json

import run as harness


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    harness.prepare()
    for seed in (int(s) for s in args.seeds.split(",")):
        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=0, trace=0, rehearse=args.rehearse)
        cell = harness.Cell(bench, ns)
        harness.device_info(cell)
        driver = harness.load_module("drivers", cell.traffic["driver"])
        rows = driver.control(cell)
        for c in rows:
            print("control seed %d %-40s %-12.6g limit %-8s %s" % (
                seed, c["name"], c["value"], c["limit"],
                "ok" if c["ok"] else "FAILED"), flush=True)
        print(json.dumps({"seed": seed, "control_correct":
                          all(c["ok"] for c in rows),
                          "checks": rows}), flush=True)


if __name__ == "__main__":
    main()
