#!/usr/bin/env python
"""mx.analyze CLI — static hot-path hazard analysis (docs/ANALYZE.md).

Runs the nine analysis passes over ``mxnet_tpu/`` and fails on:

* any unwaived finding;
* any mxnet_tpu/pallas/ kernel wrapper with no interpret-mode parity
  test named in ``tests/`` (``check_kernel_parity``);
* any waiver without a reason, or matching no finding (unused);
* drift between the live waiver set and the committed baseline
  (``tools/static_baseline.json``).

Usage:
    python tools/check_static.py                 # full run (tier-1)
    python tools/check_static.py --changed       # only files changed
                                                 #   vs main (fast)
    python tools/check_static.py --update-baseline
    python tools/check_static.py --update-config # regen docs/CONFIG.md
    python tools/check_static.py --list-passes
    python tools/check_static.py --show-waived   # baseline as text

Stdlib-only: loads the analyzer sub-package alone, by path
(tools/_analyze.py), so neither jax nor the mxnet_tpu runtime is ever
imported — safe and <15 s as a tier-1 subprocess on a
1-core container.
"""
import argparse
import ast
import glob
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _analyze import ROOT, analyze                # noqa: E402
from analyze import envknobs as _envknobs         # noqa: E402

BASELINE = os.path.join(ROOT, "tools", "static_baseline.json")
CONFIG_DOC = os.path.join(ROOT, "docs", "CONFIG.md")


def changed_paths():
    """Package files changed vs main (committed + working tree)."""
    paths = set()
    for cmd in (["git", "diff", "--name-only", "main...HEAD"],
                ["git", "diff", "--name-only", "HEAD"],
                ["git", "ls-files", "--others", "--exclude-standard"]):
        try:
            out = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                                 text=True, timeout=30).stdout
        except Exception:
            continue
        for line in out.splitlines():
            line = line.strip()
            if line.startswith("mxnet_tpu/") and line.endswith(".py"):
                paths.add(line)
    return sorted(paths)


def update_config_doc(ctx):
    """Regenerate docs/CONFIG.md, preserving Description cells."""
    reads = _envknobs.collect_env_reads(ctx)
    old_desc = {}
    if os.path.exists(CONFIG_DOC):
        with open(CONFIG_DOC) as f:
            for line in f:
                m = _envknobs._ROW.match(line)
                if m:
                    cells = [c.strip() for c in line.split("|")]
                    # | `NAME` | where | description |
                    if len(cells) >= 4:
                        old_desc[m.group(1)] = cells[3]
    lines = [
        "# Environment knobs (generated)",
        "",
        "Every `MXNET_*`/`MXTPU_*` variable read anywhere in",
        "`mxnet_tpu/` — coverage is enforced both directions by",
        "`tools/check_static.py` (the `envknobs` pass, same",
        "discipline as the telemetry glossary in",
        "[OBSERVABILITY.md](OBSERVABILITY.md)).  Regenerate the",
        "table with `python tools/check_static.py --update-config`;",
        "Description cells are hand-written and preserved.",
        "",
        "| Knob | Read at | Description |",
        "|---|---|---|",
    ]
    for name in sorted(reads):
        sites = reads[name]
        where = ", ".join(sorted({"%s:%d" % (p.split("mxnet_tpu/")[-1],
                                             ln) for p, ln in sites}))
        if len(where) > 72:
            where = where[:69] + "..."
        desc = old_desc.get(name, "(undocumented)")
        lines.append("| `%s` | %s | %s |" % (name, where, desc))
    lines += [
        "",
        "Reference-compat `DMLC_*` variables (launcher contract) are",
        "documented in [KVSTORE.md](KVSTORE.md); accepted-but-inert",
        "reference knobs carry their rationale in `mxnet_tpu/config.py`.",
        "",
    ]
    with open(CONFIG_DOC, "w") as f:
        f.write("\n".join(lines))
    return len(reads)


def check_kernel_parity(ctx):
    """Every host wrapper in mxnet_tpu/pallas/ that constructs a
    ``pl.pallas_call`` must be exercised by name somewhere under
    ``tests/test_*.py`` — the interpret=True parity convention
    (docs/KERNELS.md): kernels run on CPU in interpret mode against
    the XLA reference in tier-1.  Deliberately grep-level: it guards
    against landing a kernel with NO test at all, not against weak
    tests."""
    test_text = ""
    for p in sorted(glob.glob(os.path.join(ROOT, "tests",
                                           "test_*.py"))):
        with open(p) as f:
            test_text += f.read()
    errors = []
    for mod in ctx.modules:
        if not mod.path.startswith("mxnet_tpu/pallas/"):
            continue
        for node in mod.tree.body:
            if not isinstance(node, ast.FunctionDef):
                continue
            has_kernel = False
            for c in ast.walk(node):
                if isinstance(c, ast.Call):
                    r = mod.resolve(c.func)
                    if r is not None and (r == "pallas_call"
                                          or r.endswith(".pallas_call")):
                        has_kernel = True
                        break
            if has_kernel and node.name not in test_text:
                errors.append(
                    "%s:%d: [kernel-parity/untested-kernel] pallas "
                    "kernel wrapper %r has no interpret-mode parity "
                    "test (its name appears in no tests/test_*.py)"
                    % (mod.path, node.lineno, node.name))
    return errors


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--changed", action="store_true",
                    help="analyze only files changed vs main "
                         "(skips baseline drift checking)")
    ap.add_argument("--update-baseline", action="store_true")
    ap.add_argument("--update-config", action="store_true")
    ap.add_argument("--list-passes", action="store_true")
    ap.add_argument("--show-waived", action="store_true")
    args = ap.parse_args(argv)

    passes = analyze.all_passes()
    if args.list_passes:
        for p in passes:
            print("%-11s %s" % (p.name, p.doc))
        return 0

    if args.changed and args.update_baseline:
        # the baseline mirrors the WHOLE repo's waiver set; writing it
        # from a changed-files-only view would silently drop every
        # other entry
        print("check_static: --update-baseline requires a full run "
              "(drop --changed)")
        return 2

    report = None
    if args.changed:
        report = changed_paths()
        if not report:
            print("check_static: no changed mxnet_tpu/*.py files")
            return 0
    ctx, findings = analyze.run(ROOT, passes, report_paths=report)

    if args.update_config:
        n = update_config_doc(ctx)
        print("check_static: wrote docs/CONFIG.md (%d knobs)" % n)
        # re-run so the doc coverage reflects the regenerated table
        ctx, findings = analyze.run(ROOT, passes, report_paths=report)

    if args.update_baseline:
        analyze.save_baseline(BASELINE, findings)
        print("check_static: wrote %s (%d waived findings)"
              % (os.path.relpath(BASELINE, ROOT),
                 sum(1 for f in findings if f.waived)))

    if args.show_waived:
        for f in findings:
            if f.waived:
                print("%s  -- %s" % (f.format(), f.waiver_reason))
        return 0

    errors = [f for f in findings if not f.waived]
    kernel_errors = check_kernel_parity(ctx)
    baseline_errors = []
    if not args.changed:
        baseline_errors = analyze.diff_baseline(
            findings, analyze.load_baseline(BASELINE))

    if errors or kernel_errors or baseline_errors:
        print("check_static: %d problem(s)"
              % (len(errors) + len(kernel_errors)
                 + len(baseline_errors)))
        for f in errors:
            print("  " + f.format())
        for e in kernel_errors:
            print("  " + e)
        for e in baseline_errors:
            print("  " + e)
        return 1
    n_waived = sum(1 for f in findings if f.waived)
    print("check_static: OK (%d files, %d passes, %d findings all "
          "waived+baselined)"
          % (len(ctx.modules), len(passes), n_waived))
    return 0


if __name__ == "__main__":
    sys.exit(main())
