"""Chief-on-TPU PS-cluster smoke (VERDICT r4 weak #3).

The 4-process MNIST PS cluster (dedicated PS task + chief + 2 gradient
workers, real gradients over the native socket service) has only ever run
with every process pinned to CPU (the pytest suite).  This tool runs the
SAME cluster with the chief's apply step on the real TPU — proving the
cross-process PS path composes with the chip and recording the chief's
measured step rate.

One process per chip: this parent never imports jax, the chief child is
the only process that may take the chip, and the PS and worker children run
with ``JAX_PLATFORMS=cpu`` (which stock libtpu honours).

Prints one JSON line {"ok": bool, "final": {...chief FINAL record...}}.
Exit 0 on pass.  Never run alongside another process that holds the chip.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _final(out: str) -> dict:
    """Parse the last 'FINAL k=v k=v ...' line (ps_experiment contract)."""
    lines = [l for l in out.splitlines() if l.startswith("FINAL ")]
    if not lines:
        raise AssertionError("no FINAL line:\n" + out[-2000:])
    d: dict = {}
    for tok in lines[-1].split()[1:]:
        k, _, v = tok.partition("=")
        try:
            d[k] = float(v)
        except ValueError:
            d[k] = v
    return d


def main():
    import argparse

    ap = argparse.ArgumentParser(
        description="4-process PS cluster, chief on the real chip (one "
        "TPU process at a time). "
        "Spawns real training processes; --help must never start them."
    )
    ap.add_argument("--train-steps", type=int, default=40)
    args = ap.parse_args()

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    cpu_env = dict(os.environ)
    cpu_env["JAX_PLATFORMS"] = "cpu"
    cpu_env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"

    import tempfile

    log_dir = tempfile.mkdtemp(prefix="ps_tpu_smoke_")
    common = [
        "--ps_emulation",
        "--batch_size=128",
        f"--train_steps={args.train_steps}",
        f"--ps_hosts=127.0.0.1:{port}",
        "--worker_hosts=wh0:1,wh1:1",
        f"--log_dir={log_dir}",
    ]

    # Each process writes its output to a file under log_dir: serially
    # communicate()-ing four PIPE'd processes deadlocks once a later
    # process fills its 64 KB pipe buffer while an earlier one is being
    # drained (ADVICE r5) — files have no backpressure, and they survive
    # for debugging when a step fails.
    log_files = {}

    def spawn(job: str, idx: int, env: dict | None):
        cmd = [
            sys.executable, os.path.join(ROOT, "examples", "mnist_mlp.py"),
            f"--job_name={job}", f"--task_index={idx}", *common,
        ]
        name = f"{job}{idx}"
        logf = open(os.path.join(log_dir, f"{name}.log"), "w")
        log_files[name] = logf
        return subprocess.Popen(
            cmd, stdout=logf, stderr=subprocess.STDOUT,
            text=True, env=env, cwd=ROOT,
        )

    procs = {"ps": spawn("ps", 0, cpu_env)}
    time.sleep(1.0)  # PS binds first (reference launch order)
    # The chief inherits the environment: the default platform, the chip.
    procs["chief"] = spawn("chief", 0, None)
    procs["w0"] = spawn("worker", 0, cpu_env)
    procs["w1"] = spawn("worker", 1, cpu_env)
    name_of = {"ps": "ps0", "chief": "chief0", "w0": "worker0", "w1": "worker1"}
    ok = True
    deadline = time.time() + 900
    try:
        for name, p in procs.items():
            try:
                p.wait(timeout=max(1.0, deadline - time.time()))
            except subprocess.TimeoutExpired:
                ok = False
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
                p.wait()
        for f in log_files.values():
            f.close()
    outs = {}
    for name in procs:
        with open(os.path.join(log_dir, f"{name_of[name]}.log")) as f:
            outs[name] = f.read()
    for name, p in procs.items():
        if p.returncode != 0:
            ok = False
            print(f"--- {name} rc={p.returncode} ---", file=sys.stderr)
            print(outs.get(name, "")[-2000:], file=sys.stderr)

    rec = {"ok": ok, "tool": "ps_tpu_smoke"}
    if ok:
        f = _final(outs["chief"])
        contributed = [
            int(outs[w].split("contributed=")[1].split()[0]) for w in ("w0", "w1")
        ]
        rec["final"] = f
        rec["worker_contributions"] = contributed
        rec["ok"] = (
            f["mode"] == "sync_replicas_cluster"
            and f["step"] >= 30
            and sum(contributed) >= 25
        )
        # The proof the chief actually ran on the chip: the chief prints
        # a scrapable CHIEF_PLATFORM=<platform> line (ps_experiment.py).
        plat = ""
        for line in outs["chief"].splitlines():
            if line.startswith("CHIEF_PLATFORM="):
                plat = line.split("=", 1)[1].strip()
        rec["chief_platform"] = plat
        rec["ok"] = rec["ok"] and plat == "tpu"
    print(json.dumps(rec))
    sys.exit(0 if rec["ok"] else 1)


if __name__ == "__main__":
    main()
