#!/usr/bin/env python
"""Static telemetry-consistency check (runs inside tier-1 via
tests/test_telemetry.py).

Since the mx.analyze framework landed this is a thin shim: the four
checks (no stray witness globals, glossary coverage both directions,
label coverage — docstring history in ``mxnet_tpu/analyze/telemetry.py``)
now run as the analyzer's ``telemetry`` pass, and the full tier-1 gate
is ``tools/check_static.py`` (all seven passes + waiver baseline).
This entry point stays so existing wiring, docs, and muscle memory
(``python tools/check_telemetry.py``) keep working; it runs ONLY the
telemetry pass and keeps the historical output shape.

Stdlib-only, no package import: safe anywhere (including as a plain
subprocess inside the test suite).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _analyze import ROOT, analyze                # noqa: E402
from analyze.telemetry import TelemetryPass       # noqa: E402


def main():
    tpass = TelemetryPass()
    ctx, findings = analyze.run(ROOT, [tpass])
    errors = [f for f in findings
              if not f.waived and f.pass_name == "telemetry"]
    if errors:
        print("check_telemetry: %d problem(s)" % len(errors))
        for f in errors:
            print("  %s:%d: %s" % (f.path, f.line, f.message))
        return 1
    # historical summary shape, counts straight from the pass's own
    # scan so they can never drift from what was actually checked
    print("check_telemetry: OK (%d series in glossary, %d registered "
          "by literal, %d label keys documented; full static gate: "
          "tools/check_static.py)"
          % (len(tpass.glossary_names), len(tpass.registered),
             len(tpass.labels_used)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
