"""Native ThreadSanitizer gate (r16), run by an operator by hand on a host
with a TSAN toolchain; needs no accelerator.

Builds ``native/libdtx_native_tsan.so`` (the ``tsan`` Makefile target:
``-fsanitize=thread -O1 -g``), then runs ``tools/tsan_driver.py`` — the
real ``ps_service`` client stack exercising a replicated PS pair with
concurrent clients, a backup kill/restart/resync and a partition/heal
cycle — with ``libtsan`` preloaded and the sanitized library selected via
``DTX_NATIVE_LIB``.  Any unsuppressed data-race warning fails the step.

Suppressions live in ``tools/tsan_suppressions.txt`` (standard TSAN
syntax, one justified entry per line) — same contract as the dtxlint
baseline: a suppression is a documented design decision with a reason in
the comment above it, and this step counts them in its verdict so a
growing pile is visible in every run.

Hosts without a TSAN toolchain (no ``libtsan`` next to g++) record a LOUD
``skipped`` verdict and exit 0 — an environmental gap is not a race, and
must not fail the way a genuine finding does.

Output: one compact JSON line (``metric: tsan_protocol``).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(ROOT, "distributed_tensorflow_examples_tpu", "native")
TSAN_LIB = os.path.join(NATIVE, "libdtx_native_tsan.so")
SUPPRESSIONS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "tsan_suppressions.txt")

_WARNING_RE = re.compile(r"^WARNING: ThreadSanitizer: (.+?) \(", re.M)
_SUMMARY_RE = re.compile(r"^SUMMARY: ThreadSanitizer: (.+)$", re.M)


def find_libtsan() -> str | None:
    """The runtime to LD_PRELOAD, via the compiler's own search path."""
    for name in ("libtsan.so.2", "libtsan.so.1", "libtsan.so.0"):
        try:
            out = subprocess.run(
                ["gcc", "-print-file-name=" + name],
                capture_output=True, text=True, timeout=30,
            ).stdout.strip()
        except (OSError, subprocess.TimeoutExpired):
            return None
        if out and os.path.isabs(out) and os.path.exists(out):
            return out
    return None


def suppression_count() -> int:
    if not os.path.exists(SUPPRESSIONS):
        return 0
    return sum(
        1 for line in open(SUPPRESSIONS)
        if line.strip() and not line.strip().startswith("#")
    )


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=8.0,
                    help="driver load duration (sanitized time)")
    ap.add_argument("--timeout", type=float, default=420.0)
    args = ap.parse_args()
    t0 = time.time()

    def emit(doc: dict, rc: int) -> int:
        doc.setdefault("metric", "tsan_protocol")
        doc["seconds"] = round(time.time() - t0, 1)
        doc["suppressions"] = suppression_count()
        print(json.dumps(doc, separators=(",", ":")))
        return rc

    libtsan = find_libtsan()
    if libtsan is None:
        return emit({"ok": False, "skipped": "no libtsan next to gcc — "
                     "TSAN gate not runnable on this host"}, 0)
    try:
        build = subprocess.run(
            ["make", "-s", "tsan"], cwd=NATIVE, capture_output=True,
            text=True, timeout=420,
        )
    except subprocess.TimeoutExpired:
        # The one-compact-JSON-line contract holds on EVERY exit path —
        # a hung build must still produce a diagnosable verdict, not a
        # traceback with NO JSON.
        return emit({"ok": False, "error": "tsan build timed out"}, 1)
    if build.returncode != 0:
        # The toolchain is PRESENT (libtsan found above), so a failing
        # build is a code/Makefile regression, not an environmental gap —
        # it must fail the step, or one bad commit disables the race gate
        # forever behind a green verdict.
        return emit({
            "ok": False,
            "error": f"tsan build failed (rc {build.returncode}): "
            + build.stderr.strip()[-500:],
        }, 1)

    env = dict(os.environ)
    env["LD_PRELOAD"] = libtsan
    env["DTX_NATIVE_LIB"] = TSAN_LIB
    env["TSAN_OPTIONS"] = ":".join([
        f"suppressions={SUPPRESSIONS}" if os.path.exists(SUPPRESSIONS) else "",
        "halt_on_error=0", "exitcode=66", "history_size=7",
    ]).strip(":")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools", "tsan_driver.py"),
             "--seconds", str(args.seconds)],
            capture_output=True, text=True, cwd=ROOT, env=env,
            timeout=args.timeout,
        )
    except subprocess.TimeoutExpired:
        return emit({"ok": False, "error": "driver timed out under TSAN"}, 1)
    warnings = _WARNING_RE.findall(proc.stderr)
    summaries = sorted(set(_SUMMARY_RE.findall(proc.stderr)))
    driver_ok = "TSAN_DRIVER_OK" in proc.stdout
    ok = driver_ok and not warnings and proc.returncode == 0
    doc = {
        "ok": ok,
        "warnings": len(warnings),
        "warning_kinds": sorted(set(warnings)),
        "summaries": summaries[:20],
        "driver_rc": proc.returncode,
        "driver_line": next(
            (ln for ln in proc.stdout.splitlines()
             if ln.startswith("TSAN_DRIVER_OK")), "",
        ),
    }
    if not driver_ok:
        doc["stderr_tail"] = proc.stderr[-1500:]
    return emit(doc, 0 if ok else 1)


if __name__ == "__main__":
    sys.exit(main())
