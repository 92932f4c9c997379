"""The load generator: a process of its own, which never takes the chip.

Started by the process that holds the chip as its first act, so that the
imports overlap the replica's set-up, with ``JAX_PLATFORMS=cpu`` in its
environment before anything is imported.  It says ``ready`` on its standard
output, is told on its standard input where the schedule is and then when
it starts and ends (``CLOCK_MONOTONIC`` is shared by the processes of
one machine), plays the schedule against the replica over loopback with the
program's own ``ServeClient`` (``decode_open`` / ``decode_next`` every
``poll_s``, as ``ServeClient.generate`` does), stamps every token itself
and writes its samples when the end has come.

Run: ``python loadgen.py <result.json>``
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def play(client, req: dict, rec: dict, poll_s: float, t_end: float) -> None:
    """One request through ``decode_open`` / ``decode_next``; every poll
    that brings tokens stamps them."""
    import numpy as np

    rec["sent"] = time.monotonic()
    sid = client.decode_open(np.asarray(req["prompt"], np.int32), req["n"])
    try:
        while True:
            got, done, _step = client.decode_next(sid, cursor=len(rec["tokens"]))
            now = time.monotonic()
            if len(got):
                rec["tokens"].extend(int(t) for t in got)
                rec["times"].extend([now] * len(got))
            if done:
                rec["status"] = "done"
                return
            if now >= t_end:
                rec["status"] = "open"
                return
            time.sleep(poll_s)
    finally:
        client.decode_close(sid)


def run(schedule: dict, t0: float, t_end: float) -> list[dict]:
    from distributed_tensorflow_examples_tpu.serve import client as client_lib

    host, port, poll_s = schedule["host"], schedule["port"], schedule["poll_s"]
    records = [
        {"id": r["id"], "due": None, "sent": None, "tokens": [], "times": [],
         "status": "unsent", "error": None}
        for r in schedule["requests"]
    ]

    def guarded(client, req, rec):
        try:
            play(client, req, rec, poll_s, t_end)
        except (client_lib.ServeError, OSError) as e:
            rec["status"], rec["error"] = "failed", f"{type(e).__name__}: {e}"

    def one_user(req, rec):
        client = client_lib.ServeClient(host, port, role=f"bench_user{req['id']}")
        try:
            guarded(client, req, rec)
        finally:
            client.close()

    def one_caller(idx, mine):
        client = client_lib.ServeClient(host, port, role=f"bench_caller{idx}")
        try:
            for req in mine:
                if time.monotonic() >= t_end:
                    return
                rec = records[req["id"]]
                rec["due"] = time.monotonic()
                guarded(client, req, rec)
        finally:
            client.close()

    threads = []
    if schedule["kind"] == "serve-open":
        for req in schedule["requests"]:
            due = t0 + req["due_s"]
            if due >= t_end:
                break
            delay = due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            records[req["id"]]["due"] = due
            th = threading.Thread(
                target=one_user, args=(req, records[req["id"]]), daemon=True
            )
            th.start()
            threads.append(th)
    else:
        by_client: dict[int, list] = {}
        for req in schedule["requests"]:
            by_client.setdefault(req["client"], []).append(req)
        delay = t0 - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        for idx, mine in sorted(by_client.items()):
            th = threading.Thread(target=one_caller, args=(idx, mine), daemon=True)
            th.start()
            threads.append(th)
    for th in threads:
        th.join(timeout=max(0.0, t_end - time.monotonic()) + 30.0)
    return [r for r in records if r["status"] != "unsent"]


def main(argv) -> int:
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("the load generator must not see the chip: JAX_PLATFORMS=cpu")
    (result_path,) = argv
    # Import the client before saying ready: the schedule must not wait
    # for an import.
    from distributed_tensorflow_examples_tpu.serve import client  # noqa: F401

    print("ready", flush=True)
    with open(sys.stdin.readline().strip()) as f:
        schedule = json.load(f)
    t0, t_end = (float(x) for x in sys.stdin.readline().split())
    records = run(schedule, t0, t_end)
    tmp = result_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"records": records}, f)
    os.replace(tmp, result_path)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
