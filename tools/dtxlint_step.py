"""dtxlint as one timed command (r11; wall-time metric r16), run by tier-1
(``tests/test_dtxlint.py``) and by an operator by hand.

It runs the passes through the library, emits the ``--json --compact``
document EXTENDED with ``metric: "dtxlint"`` and the run's ``seconds`` as
its single output line, and exits with the CLI's code.  The test bounds
``seconds``, so a new pass that silently blows up lint wall-time — and with
it tier-1's repo-gate — fails loudly instead.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools.dtxlint import (  # noqa: E402
    LintConfig, apply_baseline, load_baseline, run_passes,
)
from tools.dtxlint.__main__ import build_report  # noqa: E402


def main() -> int:
    t0 = time.time()
    baseline_path = os.path.join(ROOT, "tools", "dtxlint_baseline.json")
    try:
        baseline = load_baseline(baseline_path)
        results = run_passes(LintConfig.default(ROOT))
    except (OSError, ValueError, SyntaxError) as e:
        print(json.dumps({
            "metric": "dtxlint", "ok": False, "error": str(e),
            "seconds": round(time.time() - t0, 2),
        }, separators=(",", ":")))
        return 2
    active, suppressed, stale = apply_baseline(results, baseline)
    report = build_report(results, active, suppressed, stale, baseline_path)
    report["metric"] = "dtxlint"
    report["seconds"] = round(time.time() - t0, 2)
    print(json.dumps(report, separators=(",", ":")))
    return 0 if (not active and not stale) else 1


if __name__ == "__main__":
    sys.exit(main())
