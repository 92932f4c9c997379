"""Multi-process test harness: the ``MultiProcessRunner`` analog
(SURVEY.md section 4: ``TF/python/distribute/multi_process_runner.py:107``).

Forks one real OS process per cluster task, injects cluster identity via
``TF_CONFIG`` (exercising ``parallel.dist``'s resolver exactly as a reference
launcher would), captures per-task logs, and supports killing a task mid-run
— the fault-injection primitive the reference's harness provides for testing
recovery behavior.

Workers are plain Python scripts (source string or file).  The harness runs
them on the multi-process CPU backend (gloo collectives), giving each process
one CPU device — a real 2+-process cluster without TPU hardware.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


_WORKER_PRELUDE = """\
import os
import sys
import jax
sys.path.insert(0, {repo_root!r})
from distributed_tensorflow_examples_tpu.parallel import dist
_cluster = dist.initialize()
"""


class MultiProcessRunner:
    """Launch ``num_processes`` copies of ``worker_src`` as a TF_CONFIG
    cluster; each copy runs after a ``dist.initialize()`` prelude (so the
    script body sees a live multi-process JAX runtime).

    Usage::

        r = MultiProcessRunner(2, "print(jax.process_count())")
        results = r.run()          # or: r.start(); ...; r.join()
    """

    def __init__(
        self,
        num_processes: int,
        worker_src: str,
        *,
        env: dict[str, str] | None = None,
        timeout: float = 120.0,
        prelude: bool = True,
        fault_plan: str | None = None,
    ):
        """``prelude=False`` skips the ``dist.initialize()`` header: the task
        script manages (or delegates) cluster bootstrap itself — e.g. a
        supervisor task whose *child* joins the coordination service.

        ``fault_plan`` sets ``DTX_FAULT_PLAN`` for every task (see
        ``utils.faults``); each task additionally gets a default fault role
        ``task<i>`` via ``DTX_FAULT_ROLE`` (overridable through ``env``),
        so a plan can target one task of the cluster.  The harness's own
        ``kill_task`` remains the out-of-band SIGKILL fault.

        Every task runs with ``JAX_PLATFORMS=cpu``: this runner IS the fake
        localhost cluster (SURVEY.md section 4), and a chip belongs to one
        process at a time."""
        self.n = num_processes
        self.timeout = timeout
        self.port = _free_port()
        self._dir = tempfile.mkdtemp(prefix="dtx_mp_")
        repo_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        header = _WORKER_PRELUDE.format(repo_root=repo_root) if prelude else (
            f"import sys\nsys.path.insert(0, {repo_root!r})\n"
        )
        script = header + worker_src
        self.script_path = os.path.join(self._dir, "worker.py")
        with open(self.script_path, "w") as f:
            f.write(script)
        self.extra_env = dict(env or {})
        if fault_plan is not None:
            self.extra_env.setdefault("DTX_FAULT_PLAN", fault_plan)
        self.procs: list[subprocess.Popen] = []
        self.log_paths: list[str] = []
        self._log_files: list = []

    def _tf_config(self, index: int) -> str:
        # Every entry carries the coordinator's port: only workers[0] (the
        # coordinator) binds it, the rest just dial it.
        return json.dumps(
            {
                "cluster": {"worker": [f"localhost:{self.port}"] * self.n},
                "task": {"type": "worker", "index": index},
            }
        )

    def start(self) -> None:
        for i in range(self.n):
            env = dict(os.environ)
            env.pop("XLA_FLAGS", None)  # no virtual-device leakage from pytest
            env["JAX_PLATFORMS"] = "cpu"
            env["TF_CONFIG"] = self._tf_config(i)
            env["DTX_FAULT_ROLE"] = f"task{i}"
            env.update(self.extra_env)
            log_path = os.path.join(self._dir, f"task_{i}.log")
            self.log_paths.append(log_path)
            logf = open(log_path, "w")
            self._log_files.append(logf)
            self.procs.append(
                subprocess.Popen(
                    [sys.executable, self.script_path, str(i)],
                    env=env,
                    stdout=logf,
                    stderr=subprocess.STDOUT,
                )
            )

    def kill_task(self, index: int, sig: int = signal.SIGKILL) -> None:
        """Fault injection: kill one task (the reference harness's
        ``terminate`` used to test preemption/recovery)."""
        self.procs[index].send_signal(sig)

    def join(self, timeout: float | None = None) -> list[int]:
        """Wait for all tasks; returns per-task return codes (negative =
        killed by signal).  Tasks still running at timeout are killed and
        reported as -9."""
        deadline = time.monotonic() + (timeout or self.timeout)
        codes: list[int | None] = [None] * self.n
        while time.monotonic() < deadline and any(c is None for c in codes):
            for i, p in enumerate(self.procs):
                if codes[i] is None:
                    codes[i] = p.poll()
            time.sleep(0.05)
        for i, p in enumerate(self.procs):
            if codes[i] is None:
                p.kill()
                p.wait()
                codes[i] = -9
        for f in self._log_files:
            try:
                f.close()
            except OSError:
                pass
        self._log_files.clear()
        return [int(c) for c in codes]

    def cleanup(self) -> None:
        """Remove the temp worker-script/log directory (call after a
        successful run; kept on failure for debugging)."""
        import shutil

        shutil.rmtree(self._dir, ignore_errors=True)

    def output(self, index: int) -> str:
        with open(self.log_paths[index]) as f:
            return f.read()

    def run(self) -> list[str]:
        """start + join; raises if any task failed; returns per-task logs."""
        self.start()
        codes = self.join()
        if any(c != 0 for c in codes):
            logs = "\n".join(
                f"--- task {i} (exit {codes[i]}) ---\n{self.output(i)}"
                for i in range(self.n)
            )
            raise RuntimeError(f"multi-process run failed: {codes}\n{logs}")
        outs = [self.output(i) for i in range(self.n)]
        self.cleanup()
        return outs
