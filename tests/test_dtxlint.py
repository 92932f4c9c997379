"""dtxlint (r11): the repo must lint clean, and each pass must actually
catch the violation class it exists for.

Two layers:

- **Repo gate** — ``python -m tools.dtxlint`` over the real tree exits 0
  with no active findings and no stale suppressions.  This is the tier-1
  guardrail the unified-runtime/replication refactors (ROADMAP 1–2) lean
  on: an opcode renumbering, a new blocking call under a lock, an
  uncovered fault role or a drifted flag fails CI here, not in
  production.
- **Detector proofs** — synthetic mini-repo fixtures, one injected
  violation per test, asserting the exact finding code fires.  A linter
  whose checks silently stopped matching (AST shape drift, regex rot) is
  worse than no linter — these tests are the linter's linter.
"""

from __future__ import annotations

import json
import os
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools import dtxlint  # noqa: E402
from tools.dtxlint import LintConfig, apply_baseline, load_baseline  # noqa: E402
from tools.dtxlint.__main__ import main as dtxlint_main  # noqa: E402

# ---------------------------------------------------------------------------
# Synthetic fixture repo: minimal but CLEAN under all four passes.  Each
# test overrides exactly one file to inject exactly one violation.
# ---------------------------------------------------------------------------

_WIRE_PY = textwrap.dedent(
    '''
    WIRE_VERSION = 2
    HELLO_SHARD_ID_SHIFT = 8
    HELLO_SHARD_COUNT_SHIFT = 24
    HELLO_SHARD_MASK = 0xFFFF
    HELLO_LAYOUT_SHIFT = 40
    HELLO_LAYOUT_MASK = 0xFF
    HELLO_REPL_SHIFT = 50
    HELLO_SHARD_MISMATCH = -5
    REPL_REFUSED = -6
    REPL_DIVERGED = -7
    WRONG_SERVICE_BASE = -40
    SERVICE_IDS = {"ps": 1, "dsvc": 2, "msrv": 3}
    PS_OPS = {"PING": 15, "PSTORE_GET": 18, "HELLO": 26}
    DSVC_OPS = {"HELLO": 26, "GET_BATCH": 67}
    SRV_OPS = {"HELLO": 26, "PREDICT": 96}
    DSVC_STATUS = {"OK": 0, "ERR": -2}
    SRV_STATUS = {"ERR": -2, "OVERLOAD": -7}
    CONTROL_OPS = {
        "ps": frozenset({"HELLO", "PING"}),
        "dsvc": frozenset({"HELLO"}),
        "msrv": frozenset({"HELLO"}),
    }
    TENANT_KEY_PREFIX = "t."
    TENANT_SCOPED_OPS = {"ps": frozenset({"PSTORE_GET"})}
    WIRE_PROTOCOLS = {
        "hello-first": {
            "kind": "first_op", "services": ["dsvc", "msrv"], "op": "HELLO",
        },
        "ping-session": {
            "kind": "session", "service": "ps", "init": "idle",
            "transitions": {
                "idle": {"PING": "pinged"},
                "pinged": {"PING": "pinged", "PSTORE_GET": "idle"},
            },
        },
        "ping-before-get": {
            "kind": "order", "service": "ps",
            "first": "PING", "then": "PSTORE_GET",
        },
    }
    '''
)

_PS_SERVER_CC = textwrap.dedent(
    """
    constexpr int kWireVersion = 2;
    constexpr int kHelloShardIdShift = 8;
    constexpr int kHelloShardCountShift = 24;
    constexpr int kHelloShardMask = 0xFFFF;
    constexpr int kHelloLayoutShift = 40;
    constexpr int kHelloLayoutMask = 0xFF;
    constexpr int kHelloReplShift = 50;
    constexpr int kReplRefused = -6;
    constexpr int kReplDiverged = -7;
    constexpr int kTagWorkerShift = 40;
    constexpr char kTenantKeyPrefix[] = "t.";
    enum Op : int {
      PING = 15,
      PSTORE_GET = 18,
      HELLO = 26,
    };
    constexpr Op kControlOps[] = {
        HELLO, PING,
    };
    constexpr bool is_control_op(int op) {
      for (int c : kControlOps)
        if (op == c) return true;
      return false;
    }
    int dispatch(int op) {
      int status = 0;
      if (!is_control_op(op)) status += 0;  // requests counter branch
      switch (op) {
        case PING:
          break;
        case PSTORE_GET:
          break;
        case HELLO:
          status = -5 - 1;  // shard-identity mismatch answer
          break;
      }
      return status;
    }
    """
)

_NATIVE_INIT_PY = textwrap.dedent(
    """
    def _tag(worker, seq):
        assert 0 <= worker < (1 << 23)
        return (worker << 40) | seq
    """
)

_PS_SERVICE_PY = textwrap.dedent(
    '''
    from . import wire

    _PING = wire.PS_OPS["PING"]
    _PSTORE_GET = wire.PS_OPS["PSTORE_GET"]
    _HELLO = wire.PS_OPS["HELLO"]


    class PSClient:
        def ping(self):
            return self.call(_PING, 0, 0)

        def get(self):
            return self.call(_PSTORE_GET, 0, 0)

        def hello(self):
            return self.call(_HELLO, 0, 0)
    '''
)

_DSVC_PY = textwrap.dedent(
    '''
    import socket

    from . import wire

    DSVC_HELLO = wire.DSVC_OPS["HELLO"]
    DSVC_GET_BATCH = wire.DSVC_OPS["GET_BATCH"]
    OK = wire.DSVC_STATUS["OK"]
    ERR = wire.DSVC_STATUS["ERR"]

    _DSVC_CONTROL_OPS = frozenset(
        wire.DSVC_OPS[n] for n in wire.CONTROL_OPS["dsvc"]
    )


    class DataServer:
        def handle(self, op):
            counted = op not in _DSVC_CONTROL_OPS
            if op == DSVC_GET_BATCH:
                return OK
            if op == DSVC_HELLO:
                return OK
            return ERR


    class DataServiceClient:
        def _connect(self):
            sock = socket.create_connection(("h", 1))
            self._sock = sock
            self._attempt(DSVC_HELLO, 0)

        def _attempt(self, op, a):
            return OK

        def get_batch(self):
            status = self.call(DSVC_GET_BATCH, 0)
            if status == ERR:
                raise RuntimeError("err")
            assert status == OK
            return status
    '''
)

_MSRV_PY = textwrap.dedent(
    '''
    from . import wire

    SRV_HELLO = wire.SRV_OPS["HELLO"]
    SRV_PREDICT = wire.SRV_OPS["PREDICT"]
    ERR = wire.SRV_STATUS["ERR"]

    _SRV_CONTROL_OPS = frozenset(
        wire.SRV_OPS[n] for n in wire.CONTROL_OPS["msrv"]
    )


    class ModelReplicaServer:
        def handle(self, op):
            counted = op not in _SRV_CONTROL_OPS
            if op == SRV_PREDICT:
                return 0
            if op == SRV_HELLO:
                return 0
            return ERR
    '''
)

_SERVE_CLIENT_PY = textwrap.dedent(
    '''
    from . import wire

    SRV_PREDICT = wire.SRV_OPS["PREDICT"]
    ERR = wire.SRV_STATUS["ERR"]
    OVERLOAD = wire.SRV_STATUS["OVERLOAD"]


    class ServeClient:
        def predict(self):
            status = self.call(SRV_PREDICT, 0)
            if status == OVERLOAD:
                raise RuntimeError("overload")
            if status == ERR:
                raise RuntimeError("err")
            return status
    '''
)

_CONC_PY = textwrap.dedent(
    """
    import threading
    import time


    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self._aux_lock = threading.Lock()

        def step(self):
            with self._lock:
                x = 1
            time.sleep(0.0)
            return x

        def both(self):
            with self._lock:
                with self._aux_lock:
                    return 2
    """
)

_FAULTS_PY = textwrap.dedent(
    """
    _CLIENT_KINDS = ("drop_conn", "delay")
    _KINDS = _CLIENT_KINDS + ("die",)


    def control_op_codes(wire):
        return {
            code
            for names in wire.CONTROL_OPS.values()
            for code in names
        }
    """
)

_ROLES_PY = textwrap.dedent(
    """
    def make_clients(role, shard):
        prefetch = f"{role}_pf"
        data = role + "_ds"
        per_shard = f"{role}_s{shard}"
        return prefetch, data, per_shard
    """
)

_FAULT_TESTS_PY = textwrap.dedent(
    """
    PLANS = [
        "drop_conn:role=worker0_pf",
        "delay:role=worker0_ds,ms=5",
        "die:role=ps0,after_reqs=3",
        "drop_conn:role=worker0_s1",
    ]
    """
)

_FLAGS_PY = textwrap.dedent(
    '''
    from absl import flags

    FLAGS = flags.FLAGS


    def _define(kind, name, default, help_):
        getattr(flags, "DEFINE_" + kind)(name, default, help_)


    _define("integer", "train_steps", 100, "steps to run")
    _define("string", "ps_hosts", "", "parameter server hostports")
    '''
)

_FLAG_USE_PY = textwrap.dedent(
    """
    from utils.flags import FLAGS


    def main():
        print(FLAGS.train_steps)
        print(FLAGS.ps_hosts)
    """
)

_RUNBOOK_MD = textwrap.dedent(
    """
    # Runbook

    Run with `--train_steps` and point `--ps_hosts` at the servers.
    """
)

_FILES = {
    "pkg/parallel/wire.py": _WIRE_PY,
    "pkg/native/ps_server.cc": _PS_SERVER_CC,
    "pkg/native/__init__.py": _NATIVE_INIT_PY,
    "pkg/parallel/ps_service.py": _PS_SERVICE_PY,
    "pkg/data/data_service.py": _DSVC_PY,
    "pkg/serve/model_server.py": _MSRV_PY,
    "pkg/serve/client.py": _SERVE_CLIENT_PY,
    "pkg/conc/worker.py": _CONC_PY,
    "pkg/utils/faults.py": _FAULTS_PY,
    "pkg/roles/transport.py": _ROLES_PY,
    "tests/test_faults.py": _FAULT_TESTS_PY,
    "pkg/utils/flags.py": _FLAGS_PY,
    "use/consume.py": _FLAG_USE_PY,
    "RUNBOOK.md": _RUNBOOK_MD,
}


def make_cfg(tmp_path: Path, overrides: dict[str, str] | None = None) -> LintConfig:
    """Write the fixture repo (plus per-test overrides) and wire a
    LintConfig at it."""
    files = dict(_FILES)
    files.update(overrides or {})
    for rel, content in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(content)
    pkg = tmp_path / "pkg"
    return LintConfig(
        root=tmp_path,
        wire_py=pkg / "parallel" / "wire.py",
        ps_server_cc=pkg / "native" / "ps_server.cc",
        native_init_py=pkg / "native" / "__init__.py",
        ps_service_py=pkg / "parallel" / "ps_service.py",
        service_files=[
            pkg / "parallel" / "ps_service.py",
            pkg / "data" / "data_service.py",
            pkg / "serve" / "model_server.py",
            pkg / "serve" / "client.py",
        ],
        dsvc_py=pkg / "data" / "data_service.py",
        msrv_py=pkg / "serve" / "model_server.py",
        serve_client_py=pkg / "serve" / "client.py",
        concurrency_dirs=[pkg / "conc"],
        faults_py=pkg / "utils" / "faults.py",
        role_source_dirs=[pkg / "roles"],
        fault_test_files=[tmp_path / "tests" / "test_faults.py"],
        flags_py=pkg / "utils" / "flags.py",
        runbook_md=tmp_path / "RUNBOOK.md",
        flag_reference_dirs=[tmp_path / "use"],
    )


def run_pass(tmp_path, pass_name, overrides=None):
    cfg = make_cfg(tmp_path, overrides)
    return dtxlint.run_passes(cfg, only=pass_name)[pass_name]


def codes(findings):
    return {f.code for f in findings}


# ---------------------------------------------------------------------------
# The fixture itself must be clean — otherwise every injection test below
# proves nothing.
# ---------------------------------------------------------------------------


def test_fixture_repo_is_clean(tmp_path):
    cfg = make_cfg(tmp_path)
    results = dtxlint.run_passes(cfg)
    flat = [f for fs in results.values() for f in fs]
    assert flat == [], [f.to_dict() for f in flat]


# ---------------------------------------------------------------------------
# Pass 1: wire conformance
# ---------------------------------------------------------------------------


def test_wire_detects_python_cpp_number_drift(tmp_path):
    findings = run_pass(tmp_path, "wire", {
        "pkg/native/ps_server.cc": _PS_SERVER_CC.replace("PING = 15", "PING = 16"),
    })
    drift = [f for f in findings if f.code == "op-drift"]
    assert len(drift) == 1 and drift[0].symbol == "PING"
    assert "15" in drift[0].message and "16" in drift[0].message


def test_wire_detects_missing_enum_entry(tmp_path):
    cc = _PS_SERVER_CC.replace("  PSTORE_GET = 18,\n", "").replace(
        "    case PSTORE_GET:\n      break;\n", ""
    )
    findings = run_pass(tmp_path, "wire", {"pkg/native/ps_server.cc": cc})
    # Gone from the enum (op-missing) AND the client still sends it with no
    # C++ case to land on (dispatch-missing).
    assert {"op-missing", "dispatch-missing"} <= codes(findings)


def test_wire_detects_undispatched_enum_op(tmp_path):
    cc = _PS_SERVER_CC.replace("    case PSTORE_GET:\n      break;\n", "")
    findings = run_pass(tmp_path, "wire", {"pkg/native/ps_server.cc": cc})
    missing = [f for f in findings if f.code == "case-missing"]
    assert [f.symbol for f in missing] == ["PSTORE_GET"]


def test_wire_detects_layout_const_drift(tmp_path):
    findings = run_pass(tmp_path, "wire", {
        "pkg/native/ps_server.cc": _PS_SERVER_CC.replace(
            "kWireVersion = 2", "kWireVersion = 3"
        ),
    })
    assert any(
        f.code == "const-drift" and f.symbol == "WIRE_VERSION" for f in findings
    )


def test_wire_parses_last_enum_entry_without_trailing_comma(tmp_path):
    """The final C++ enum member is legal without a trailing comma —
    dropping it would misreport the op as absent from the enum."""
    cc = _PS_SERVER_CC.replace("  HELLO = 26,\n", "  HELLO = 26\n")
    findings = run_pass(tmp_path, "wire", {"pkg/native/ps_server.cc": cc})
    assert findings == []


def test_wire_detects_cross_service_op_collision(tmp_path):
    # DSVC claims 96, which SRV_OPS already owns for PREDICT.
    wire = _WIRE_PY.replace('"GET_BATCH": 67', '"GET_BATCH": 96')
    findings = run_pass(tmp_path, "wire", {"pkg/parallel/wire.py": wire})
    coll = [f for f in findings if f.code == "op-collision"]
    assert coll and any("96" in f.message for f in coll)


def test_wire_shared_hello_code_point_is_not_a_collision(tmp_path):
    findings = run_pass(tmp_path, "wire")
    assert not any("HELLO" in f.symbol for f in findings if f.code == "op-collision")


def test_wire_detects_duplicate_error_status(tmp_path):
    wire = _WIRE_PY.replace('"OVERLOAD": -7', '"OVERLOAD": -2')
    findings = run_pass(tmp_path, "wire", {"pkg/parallel/wire.py": wire})
    assert "status-collision" in codes(findings)


def test_wire_wrong_service_band_excludes_its_base(tmp_path):
    """Wrong-service answers are base - id for ids 1..N: the base itself
    (-40 here) is unreserved and must not be a false collision, while
    base-1 (-41) is inside the band."""
    wire_ok = _WIRE_PY.replace('"ERR": -2}', '"ERR": -2, "FULL": -40}')
    dsvc = _DSVC_PY.replace(
        'ERR = wire.DSVC_STATUS["ERR"]',
        'ERR = wire.DSVC_STATUS["ERR"]\nFULL = wire.DSVC_STATUS["FULL"]',
    ).replace(
        "if status == ERR:",
        "if status == FULL:\n            pass\n        if status == ERR:",
    )
    findings = run_pass(tmp_path, "wire", {
        "pkg/parallel/wire.py": wire_ok, "pkg/data/data_service.py": dsvc,
    })
    assert not any(f.code == "status-collision" for f in findings)
    wire_bad = _WIRE_PY.replace('"ERR": -2}', '"ERR": -2, "FULL": -41}')
    dsvc_bad = dsvc  # same client handling; only the number moved
    findings = run_pass(tmp_path, "wire", {
        "pkg/parallel/wire.py": wire_bad, "pkg/data/data_service.py": dsvc_bad,
    })
    assert any(
        f.code == "status-collision" and "FULL" in f.symbol for f in findings
    )


def test_wire_detects_unhandled_server_status(tmp_path):
    # The server can now answer NO_MODEL but no client branch looks at it.
    wire = _WIRE_PY.replace(
        '"OVERLOAD": -7', '"OVERLOAD": -7, "NO_MODEL": -8'
    )
    findings = run_pass(tmp_path, "wire", {"pkg/parallel/wire.py": wire})
    unhandled = [f for f in findings if f.code == "status-unhandled"]
    assert [f.symbol for f in unhandled] == ["SRV_STATUS.NO_MODEL"]


def test_wire_detects_restated_protocol_literal(tmp_path):
    msrv = _MSRV_PY.replace(
        'SRV_PREDICT = wire.SRV_OPS["PREDICT"]', "SRV_PREDICT = 96"
    )
    findings = run_pass(tmp_path, "wire", {"pkg/serve/model_server.py": msrv})
    restated = [f for f in findings if f.code == "literal-restated"]
    assert len(restated) == 1 and restated[0].symbol == "SRV_PREDICT"
    assert restated[0].line > 0


def test_wire_protocol_adjacent_config_constants_are_not_restated(tmp_path):
    """Constants that merely SHARE a prefix substring with the protocol
    namespaces (``_ACCEPT_BACKLOG``, ``_PING_INTERVAL_S``) are config, not
    restated op numbers — while a true new ``_PSTORE_*`` literal is."""
    svc = _PS_SERVICE_PY.replace(
        '_HELLO = wire.PS_OPS["HELLO"]',
        '_HELLO = wire.PS_OPS["HELLO"]\n'
        "_ACCEPT_BACKLOG = 128\n"
        "_PING_INTERVAL_S = 5\n"
        "_PSTORE_DELETE = 28",
    )
    findings = run_pass(tmp_path, "wire", {"pkg/parallel/ps_service.py": svc})
    restated = [f for f in findings if f.code == "literal-restated"]
    assert [f.symbol for f in restated] == ["_PSTORE_DELETE"]


def test_wire_detects_dispatch_missing_in_python_server(tmp_path):
    # The serve client sends STATS; the server never compares op to it.
    client = _SERVE_CLIENT_PY.replace(
        'SRV_PREDICT = wire.SRV_OPS["PREDICT"]',
        'SRV_PREDICT = wire.SRV_OPS["PREDICT"]\n'
        'SRV_STATS = wire.SRV_OPS["STATS"]',
    ) + textwrap.dedent(
        """
        class StatsProbe:
            def stats(self):
                return self.call(SRV_STATS, 0)
        """
    )
    wire = _WIRE_PY.replace('"PREDICT": 96', '"PREDICT": 96, "STATS": 97')
    findings = run_pass(tmp_path, "wire", {
        "pkg/serve/client.py": client, "pkg/parallel/wire.py": wire,
    })
    missing = [f for f in findings if f.code == "dispatch-missing"]
    assert [f.symbol for f in missing] == ["SRV_STATS"]


def test_wire_hello_dispatch_satisfied_by_the_server_core(tmp_path):
    """r17: a service hosted on the shared runtime has HELLO answered by
    the core's handler table, so the service module dropping its own
    ``op == DSVC_HELLO`` compare is correct — not dispatch-missing.  A
    module NOT on the core still must compare (the drift the check
    exists for)."""
    # Drop the dsvc server's HELLO branch: dispatch-missing fires...
    no_hello = _DSVC_PY.replace(
        "        if op == DSVC_HELLO:\n            return OK\n", ""
    )
    assert no_hello != _DSVC_PY
    findings = run_pass(
        tmp_path, "wire", {"pkg/data/data_service.py": no_hello}
    )
    missing = [f for f in findings if f.code == "dispatch-missing"]
    assert [f.symbol for f in missing] == ["DSVC_HELLO"]
    # ...a PROSE mention of the core is not hosting on it — the
    # exemption needs a real import, else a revert to a hand-rolled
    # loop that keeps a doc reference would silently lose the check...
    mentions = no_hello.replace(
        "import socket",
        "import socket\n\n# migrated off server_core pending perf work",
    )
    findings = run_pass(
        tmp_path, "wire", {"pkg/data/data_service.py": mentions}
    )
    assert [f.symbol for f in findings if f.code == "dispatch-missing"] == [
        "DSVC_HELLO"
    ]
    # ...unless the module actually hosts itself on the shared core —
    # either import spelling.
    for imp in (
        "from . import server_core",
        "from .server_core import ServerCore",
    ):
        on_core = no_hello.replace(
            "import socket", f"import socket\n\n{imp}",
        )
        findings = run_pass(
            tmp_path, "wire", {"pkg/data/data_service.py": on_core}
        )
        assert [f for f in findings if f.code == "dispatch-missing"] == []


# ---------------------------------------------------------------------------
# Pass 2: concurrency
# ---------------------------------------------------------------------------


def test_concurrency_detects_blocking_call_under_lock(tmp_path):
    conc = _CONC_PY.replace(
        "with self._lock:\n            x = 1",
        "with self._lock:\n            x = 1\n"
        "            time.sleep(0.5)",
    )
    findings = run_pass(tmp_path, "concurrency", {"pkg/conc/worker.py": conc})
    blocked = [f for f in findings if f.code == "blocking-under-lock"]
    assert len(blocked) == 1
    assert "Worker.step" in blocked[0].symbol and "sleep" in blocked[0].symbol


def test_concurrency_detects_naked_queue_get_under_lock(tmp_path):
    conc = _CONC_PY.replace(
        "with self._lock:\n            x = 1",
        "with self._lock:\n            x = self._q.get()",
    )
    findings = run_pass(tmp_path, "concurrency", {"pkg/conc/worker.py": conc})
    assert "blocking-under-lock" in codes(findings)


def test_concurrency_timeout_get_under_lock_is_clean(tmp_path):
    conc = _CONC_PY.replace(
        "with self._lock:\n            x = 1",
        "with self._lock:\n            x = self._q.get(timeout=1.0)",
    )
    findings = run_pass(tmp_path, "concurrency", {"pkg/conc/worker.py": conc})
    assert findings == []


def test_concurrency_detects_blocking_with_item_under_lock(tmp_path):
    """A blocking call used AS a with-item context expression still runs
    under the enclosing lock (`with self._lock:` then
    `with conn.accept() as c:` accepts while holding it)."""
    conc = _CONC_PY.replace(
        "with self._lock:\n            x = 1",
        "with self._lock:\n            with self._conn.accept() as x:\n"
        "                pass",
    )
    findings = run_pass(tmp_path, "concurrency", {"pkg/conc/worker.py": conc})
    blocked = [f for f in findings if f.code == "blocking-under-lock"]
    assert len(blocked) == 1 and "accept" in blocked[0].symbol


def test_concurrency_deferred_lambda_under_lock_is_clean(tmp_path):
    """A lambda BUILT under a lock runs later, lock released — flagging
    `jobs.append(lambda: q.get())` would fail the lint on the exact shape
    ps_shard's per-shard closures use."""
    conc = _CONC_PY.replace(
        "with self._lock:\n            x = 1",
        "with self._lock:\n            x = lambda: self._q.get()",
    )
    findings = run_pass(tmp_path, "concurrency", {"pkg/conc/worker.py": conc})
    assert findings == []


def test_concurrency_detects_bare_acquire_in_except_handler(tmp_path):
    """Error-recovery paths leak locks too: an unpaired acquire inside an
    except body must be found."""
    conc = _CONC_PY + textwrap.dedent(
        """

        def recover(worker):
            try:
                return compute()
            except OSError:
                worker._lock.acquire()
                return reconnect()
        """
    )
    findings = run_pass(tmp_path, "concurrency", {"pkg/conc/worker.py": conc})
    bare = [f for f in findings if f.code == "acquire-outside-with"]
    assert len(bare) == 1 and "recover" in bare[0].symbol


def test_concurrency_detects_bare_acquire(tmp_path):
    conc = _CONC_PY + textwrap.dedent(
        """

        def leaky(worker):
            worker._lock.acquire()
            value = compute()
            worker._lock.release()
            return value
        """
    )
    findings = run_pass(tmp_path, "concurrency", {"pkg/conc/worker.py": conc})
    bare = [f for f in findings if f.code == "acquire-outside-with"]
    assert len(bare) == 1 and "leaky" in bare[0].symbol


def test_concurrency_acquire_with_try_finally_is_clean(tmp_path):
    conc = _CONC_PY + textwrap.dedent(
        """

        def careful(worker):
            worker._lock.acquire()
            try:
                return compute()
            finally:
                worker._lock.release()
        """
    )
    findings = run_pass(tmp_path, "concurrency", {"pkg/conc/worker.py": conc})
    assert findings == []


def test_concurrency_nested_bare_acquire_reported_once(tmp_path):
    """A bare acquire inside a nested function belongs to the nested
    function's own lint — the enclosing function's walk must not double-
    report it under a second qualname (one defect, one baseline key)."""
    conc = _CONC_PY + textwrap.dedent(
        """

        def outer(worker):
            def inner():
                if worker:
                    worker._lock.acquire()
            return inner
        """
    )
    findings = run_pass(tmp_path, "concurrency", {"pkg/conc/worker.py": conc})
    bare = [f for f in findings if f.code == "acquire-outside-with"]
    assert len(bare) == 1 and "outer.inner" in bare[0].symbol


def test_concurrency_detects_lock_order_inversion(tmp_path):
    conc = _CONC_PY.replace(
        "def both(self):",
        textwrap.dedent(
            """\
            def inverted(self):
                    with self._aux_lock:
                        with self._lock:
                            return 3

                def both(self):"""
        ),
    )
    findings = run_pass(tmp_path, "concurrency", {"pkg/conc/worker.py": conc})
    order = [f for f in findings if f.code == "lock-order"]
    assert len(order) == 1
    assert "_lock" in order[0].symbol and "_aux_lock" in order[0].symbol


_RAW_ACCEPT_PY = textwrap.dedent(
    """\
    import socket


    class HandRolledServer:
        def loop(self):
            while True:
                conn, _ = self._listener.accept()
                self.spawn(conn)
    """
)


def test_concurrency_refuses_raw_accept_in_service_dirs(tmp_path):
    """r17: a hand-rolled accept loop in data/ or serve/ re-introduces the
    thread-per-connection server the shared core retired — refused."""
    cfg = make_cfg(tmp_path, {"pkg/data/hand_server.py": _RAW_ACCEPT_PY})
    cfg.concurrency_dirs = list(cfg.concurrency_dirs) + [
        tmp_path / "pkg" / "data", tmp_path / "pkg" / "serve",
    ]
    findings = dtxlint.run_passes(cfg, only="concurrency")["concurrency"]
    raw = [f for f in findings if f.code == "raw-accept"]
    assert len(raw) == 1
    assert raw[0].path.endswith("data/hand_server.py")
    assert "HandRolledServer.loop" in raw[0].symbol
    assert "server_core" in raw[0].message


def test_concurrency_raw_accept_outside_service_dirs_is_clean(tmp_path):
    """The core's own package (and any non-service dir) is where the one
    accept loop legitimately lives — not flagged there."""
    conc = _CONC_PY + textwrap.dedent(
        """\


        class CoreLoop:
            def accept_once(self):
                conn, _ = self._listener.accept()
                return conn
    """
    )
    findings = run_pass(tmp_path, "concurrency", {"pkg/conc/worker.py": conc})
    assert "raw-accept" not in codes(findings)


_NAKED_RETRY_PY = textwrap.dedent(
    """\
    import socket


    class Client:
        def recover(self):
            while True:
                try:
                    self._sock = socket.create_connection(self._addr)
                    return
                except OSError:
                    continue
    """
)


def test_concurrency_refuses_naked_retry_loop(tmp_path):
    """r18: a reconnect loop whose transport handler re-enters the loop
    without consulting the shared retry discipline is the metastable
    retry storm in waiting — refused."""
    findings = run_pass(
        tmp_path, "concurrency", {"pkg/conc/client.py": _NAKED_RETRY_PY}
    )
    naked = [f for f in findings if f.code == "retry-discipline"]
    assert len(naked) == 1
    assert "Client.recover" in naked[0].symbol
    assert "retry.py" in naked[0].message
    assert "try_spend" in naked[0].message


def test_concurrency_budgeted_retry_loop_is_clean(tmp_path):
    """The clean shape: the same loop consulting the shared budget (and
    jittering its backoff) passes the rule."""
    disciplined = textwrap.dedent(
        """\
        import socket
        import time

        from ..parallel import retry


        class Client:
            def __init__(self):
                self._budget = retry.RetryBudget()

            def recover(self):
                attempt = 0
                while True:
                    try:
                        self._sock = socket.create_connection(self._addr)
                        return
                    except OSError:
                        if not self._budget.try_spend():
                            raise
                        time.sleep(retry.jittered(0.25, attempt))
                        attempt += 1
        """
    )
    findings = run_pass(
        tmp_path, "concurrency", {"pkg/conc/client.py": disciplined}
    )
    assert "retry-discipline" not in codes(findings)


def test_concurrency_bounded_escape_poll_loop_is_clean(tmp_path):
    """A supervision poll whose handler counts evidence toward a bounded
    ``break`` is not a retry storm — the escape exempts it (the async_ps
    orphan-detection shape)."""
    poll = textwrap.dedent(
        """\
        import socket


        class Watcher:
            def watch(self):
                misses = 0
                while True:
                    try:
                        probe = socket.create_connection(self._peer, 0.5)
                        probe.close()
                        misses = 0
                    except OSError:
                        misses += 1
                        if misses >= 10:
                            break
                    self._tick()
        """
    )
    findings = run_pass(tmp_path, "concurrency", {"pkg/conc/watch.py": poll})
    assert "retry-discipline" not in codes(findings)


def test_concurrency_loop_without_dial_is_clean(tmp_path):
    """A loop that catches OSError but never dials (a selector/serve loop
    shape) is not a retry loop."""
    srv = textwrap.dedent(
        """\
        class Core:
            def run(self):
                while not self._stop:
                    try:
                        events = self._sel.select(0.5)
                    except OSError:
                        continue
                    self._handle(events)
        """
    )
    findings = run_pass(tmp_path, "concurrency", {"pkg/conc/core.py": srv})
    assert "retry-discipline" not in codes(findings)


# ---------------------------------------------------------------------------
# Pass 3: fault coverage
# ---------------------------------------------------------------------------


def test_fault_coverage_detects_uncovered_role_suffix(tmp_path):
    roles = _ROLES_PY.replace(
        "return prefetch, data, per_shard",
        'extra = role + "_zz"\n    return prefetch, data, per_shard, extra',
    )
    findings = run_pass(
        tmp_path, "fault_coverage", {"pkg/roles/transport.py": roles}
    )
    uncovered = [f for f in findings if f.code == "role-uncovered"]
    assert [f.symbol for f in uncovered] == ["_zz"]


def test_fault_coverage_parameterized_shard_suffix_matches_any_digit(tmp_path):
    # `_s<i>` is covered by the concrete worker0_s1 run in the matrix; drop
    # that run and the parameterized site must surface.
    tests = _FAULT_TESTS_PY.replace('    "drop_conn:role=worker0_s1",\n', "")
    findings = run_pass(
        tmp_path, "fault_coverage", {"tests/test_faults.py": tests}
    )
    assert [f.symbol for f in findings] == ["_s<i>"]


def test_fault_coverage_helper_identifier_is_not_role_coverage(tmp_path):
    """A helper named ``_dsvc_splits`` contains the substring ``_ds`` but
    is NOT a fault-matrix entry — dropping the real ``_ds`` run must still
    surface role-uncovered."""
    tests = _FAULT_TESTS_PY.replace(
        '"delay:role=worker0_ds,ms=5"', '"delay:role=worker0,ms=5"'
    ) + "\n\ndef _dsvc_splits():\n    return []\n"
    findings = run_pass(
        tmp_path, "fault_coverage", {"tests/test_faults.py": tests}
    )
    assert [f.symbol for f in findings] == ["_ds"]


def test_fault_coverage_detects_untested_fault_kind(tmp_path):
    faults = _FAULTS_PY.replace('("die",)', '("die", "pause")')
    findings = run_pass(
        tmp_path, "fault_coverage", {"pkg/utils/faults.py": faults}
    )
    uncovered = [f for f in findings if f.code == "kind-uncovered"]
    assert [f.symbol for f in uncovered] == ["pause"]


# ---------------------------------------------------------------------------
# Pass 4: flag drift
# ---------------------------------------------------------------------------


def test_flag_drift_detects_orphan_flag(tmp_path):
    flags = _FLAGS_PY + '_define("integer", "dead_knob", 0, "unused")\n'
    findings = run_pass(tmp_path, "flag_drift", {"pkg/utils/flags.py": flags})
    orphans = [f for f in findings if f.code == "flag-orphan"]
    assert [f.symbol for f in orphans] == ["dead_knob"]


def test_flag_drift_documented_but_dead_flag_is_still_orphan(tmp_path):
    """A RUNBOOK mention is documentation, not a use: it must satisfy the
    undocumented check without masking the orphan check (else a dead flag
    becomes undetectable the moment it is documented)."""
    flags = _FLAGS_PY + '_define("integer", "dead_knob", 0, "unused")\n'
    runbook = _RUNBOOK_MD + "\nAlso see `--dead_knob`.\n"
    findings = run_pass(tmp_path, "flag_drift", {
        "pkg/utils/flags.py": flags, "RUNBOOK.md": runbook,
    })
    assert [(f.code, f.symbol) for f in findings] == [("flag-orphan", "dead_knob")]


def test_flag_drift_detects_undocumented_flag(tmp_path):
    runbook = _RUNBOOK_MD.replace(" and point `--ps_hosts` at the servers", "")
    findings = run_pass(tmp_path, "flag_drift", {"RUNBOOK.md": runbook})
    undoc = [f for f in findings if f.code == "flag-undocumented"]
    assert [f.symbol for f in undoc] == ["ps_hosts"]


def test_flag_drift_detects_undefined_flag_access(tmp_path):
    use = _FLAG_USE_PY + "\n\ndef extra():\n    return FLAGS.mystery_knob\n"
    findings = run_pass(tmp_path, "flag_drift", {"use/consume.py": use})
    undef = [f for f in findings if f.code == "flag-undefined"]
    assert [f.symbol for f in undef] == ["mystery_knob"]


def test_tenant_detects_raw_prefix_fstring(tmp_path):
    """The one-injection proof: a hand-built ``f"t.{...}"`` key in a
    service module (bypassing tenancy.qualify) is refused."""
    msrv = _MSRV_PY + '\ndef bad_key(tenant, name):\n' \
        '    return f"t.{tenant}.{name}"\n'
    findings = run_pass(tmp_path, "tenant", {"pkg/serve/model_server.py": msrv})
    scope = [f for f in findings if f.code == "tenant-scope"]
    assert len(scope) == 1 and scope[0].path.endswith("model_server.py")


def test_tenant_detects_raw_tag_literal(tmp_path):
    dsvc = _DSVC_PY + '\nTAG = ",t="\n'
    findings = run_pass(tmp_path, "tenant", {"pkg/data/data_service.py": dsvc})
    assert [f.code for f in findings] == ["tenant-scope"]


def test_tenant_detects_prefix_reference_outside_tenancy(tmp_path):
    ps = _PS_SERVICE_PY + '\n_P = wire.TENANT_KEY_PREFIX\n'
    findings = run_pass(tmp_path, "tenant", {"pkg/parallel/ps_service.py": ps})
    assert [f.code for f in findings] == ["tenant-scope"]
    assert findings[0].symbol == "TENANT_KEY_PREFIX"


def test_tenant_detects_cpp_prefix_drift(tmp_path):
    cc = _PS_SERVER_CC.replace(
        'kTenantKeyPrefix[] = "t."', 'kTenantKeyPrefix[] = "T."'
    )
    findings = run_pass(tmp_path, "tenant", {"pkg/native/ps_server.cc": cc})
    assert [f.code for f in findings] == ["tenant-prefix-drift"]


def test_tenant_detects_missing_cpp_prefix(tmp_path):
    cc = _PS_SERVER_CC.replace(
        'constexpr char kTenantKeyPrefix[] = "t.";\n', ""
    )
    findings = run_pass(tmp_path, "tenant", {"pkg/native/ps_server.cc": cc})
    assert [f.code for f in findings] == ["tenant-cpp-prefix-missing"]


def test_tenant_detects_unknown_scoped_op(tmp_path):
    wire = _WIRE_PY.replace(
        'frozenset({"PSTORE_GET"})', 'frozenset({"PSTORE_NOPE"})'
    )
    findings = run_pass(tmp_path, "tenant", {"pkg/parallel/wire.py": wire})
    assert [f.code for f in findings] == ["tenant-scoped-op-unknown"]
    assert findings[0].symbol == "PSTORE_NOPE"


def test_tenant_detects_missing_registry(tmp_path):
    wire = _WIRE_PY.replace('TENANT_KEY_PREFIX = "t."\n', "").replace(
        'TENANT_SCOPED_OPS = {"ps": frozenset({"PSTORE_GET"})}\n', ""
    )
    findings = run_pass(tmp_path, "tenant", {"pkg/parallel/wire.py": wire})
    assert codes(findings) == {"tenant-registry-missing"}
    assert {f.symbol for f in findings} == {
        "TENANT_KEY_PREFIX", "TENANT_SCOPED_OPS",
    }


def test_tenant_docstring_mentions_are_clean(tmp_path):
    """Prose about the protocol (module/function docstrings naming
    ``,t=<tenant>`` shapes) is not key construction."""
    msrv = _MSRV_PY + '\ndef doc_only():\n' \
        '    """The tenant rides the name operand as a ``,t=<tenant>``\n' \
        '    tag; keys live under ``t.<tenant>.<name>``."""\n' \
        '    return None\n'
    findings = run_pass(tmp_path, "tenant", {"pkg/serve/model_server.py": msrv})
    assert findings == []


def test_flag_drift_absl_builtin_access_is_clean(tmp_path):
    use = _FLAG_USE_PY + "\n\ndef extra():\n    return FLAGS.log_dir\n"
    findings = run_pass(tmp_path, "flag_drift", {"use/consume.py": use})
    assert findings == []


# ---------------------------------------------------------------------------
# Baseline mechanics
# ---------------------------------------------------------------------------


def test_baseline_suppresses_by_key_and_reports_stale(tmp_path):
    msrv = _MSRV_PY.replace(
        'SRV_PREDICT = wire.SRV_OPS["PREDICT"]', "SRV_PREDICT = 96"
    )
    cfg = make_cfg(tmp_path, {"pkg/serve/model_server.py": msrv})
    results = dtxlint.run_passes(cfg, only="wire")
    (finding,) = results["wire"]
    active, suppressed, stale = apply_baseline(
        results, {finding.key: "pinned for the test", "wire:gone:x:y": "stale"}
    )
    assert active == [] and [f.key for f in suppressed] == [finding.key]
    assert stale == ["wire:gone:x:y"]


def test_baseline_rejects_unjustified_suppression(tmp_path):
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({"suppressions": [{"key": "wire:x:y:z"}]}))
    with pytest.raises(ValueError, match="reason"):
        load_baseline(path)


def test_baseline_rejects_non_object_document_as_value_error(tmp_path):
    """A top-level JSON array (not an object) is the same rc=2 ValueError
    path, not an AttributeError on data.get."""
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps([{"key": "w:x:y:z", "reason": "r"}]))
    with pytest.raises(ValueError, match="JSON object"):
        load_baseline(path)


@pytest.mark.parametrize("entry", [
    {"key": "wire:x:y:z", "reason": None},
    {"key": "wire:x:y:z", "reason": 7},
    {"key": None, "reason": "why"},
    "not-a-dict",
])
def test_baseline_rejects_malformed_entries_as_value_error(tmp_path, entry):
    """A hand-edited baseline with a null/number reason must surface as the
    CLI's rc=2 bad-baseline error (ValueError), never an AttributeError
    traceback that exits looking like rc=1 findings."""
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({"suppressions": [entry]}))
    with pytest.raises(ValueError):
        load_baseline(path)


def test_baseline_keys_are_line_stable(tmp_path):
    """Reformatting (line shifts) must not invalidate a suppression: the
    key has no line component."""
    msrv = _MSRV_PY.replace(
        'SRV_PREDICT = wire.SRV_OPS["PREDICT"]', "SRV_PREDICT = 96"
    )
    key1 = run_pass(tmp_path, "wire", {"pkg/serve/model_server.py": msrv})[0].key
    shifted = "\n\n\n" + msrv
    key2 = run_pass(tmp_path, "wire", {"pkg/serve/model_server.py": shifted})[0].key
    assert key1 == key2


# ---------------------------------------------------------------------------
# CLI + --json schema, and the real-repo gate
# ---------------------------------------------------------------------------


def test_cli_json_schema_and_repo_is_clean(capsys):
    """THE tier-1 gate: the real repo lints clean, and the --json document
    holds the schema external consumers parse."""
    rc = dtxlint_main(["--json", "--root", ROOT])
    report = json.loads(capsys.readouterr().out)
    assert rc == 0, report["findings"]
    assert report["schema_version"] == dtxlint.JSON_SCHEMA_VERSION == 1
    assert report["ok"] is True
    assert set(report["passes"]) == set(dtxlint.PASS_NAMES)
    assert set(report["counts"]) == {"active", "suppressed", "stale_suppressions"}
    assert report["counts"]["active"] == 0
    assert report["counts"]["stale_suppressions"] == 0
    assert report["findings"] == []
    # Suppressions carry the full finding shape so the report names what
    # was deliberately allowed.
    for f in report["suppressed"]:
        assert set(f) == {
            "key", "pass", "code", "path", "line", "symbol", "message",
        }
        assert f["key"] in {
            e["key"]
            for e in json.load(
                open(os.path.join(ROOT, "tools", "dtxlint_baseline.json"))
            )["suppressions"]
        }


def test_cli_compact_json_is_one_line(capsys):
    rc = dtxlint_main(["--json", "--compact", "--root", ROOT])
    out = capsys.readouterr().out
    assert rc == 0
    assert len(out.strip().splitlines()) == 1
    assert json.loads(out)["ok"] is True


def test_cli_findings_exit_nonzero(tmp_path, capsys):
    """A dirty tree exits 1 and renders each finding humanly."""
    make_cfg(tmp_path)  # writes the fixture tree under tmp_path
    # Point the CLI at the fixture root: the default layout misses, which
    # must be a loud rc=2 (linter failure), never a silent pass.
    rc = dtxlint_main(["--root", str(tmp_path)])
    assert rc == 2
    assert "error" in capsys.readouterr().err


def test_cli_single_pass_does_not_report_other_passes_suppressions(capsys):
    """--pass wire keeps the wire suppressions live but must not flag the
    other passes' baseline entries as stale (they did not run)."""
    rc = dtxlint_main(["--pass", "flag_drift", "--root", ROOT])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "stale" not in out.split("dtxlint:")[0]


# ---------------------------------------------------------------------------
# Pass: control plane (r16) — every exclusion site pinned to CONTROL_OPS
# ---------------------------------------------------------------------------


def test_control_detects_python_exclusion_missing_from_cpp(tmp_path):
    """Growing CONTROL_OPS['ps'] without mirroring the C++ block is the
    drifted-exclusion-set bug: the native counter keeps counting the op."""
    wire = _WIRE_PY.replace(
        '"ps": frozenset({"HELLO", "PING"})',
        '"ps": frozenset({"HELLO", "PING", "PSTORE_GET"})',
    )
    fs = run_pass(tmp_path, "control", {"pkg/parallel/wire.py": wire})
    assert codes(fs) == {"control-cpp-missing-op"}
    assert any(f.symbol == "PSTORE_GET" for f in fs)


def test_control_detects_cpp_exclusion_missing_from_python(tmp_path):
    cc = _PS_SERVER_CC.replace(
        "HELLO, PING,", "HELLO, PING, PSTORE_GET,"
    )
    fs = run_pass(tmp_path, "control", {"pkg/native/ps_server.cc": cc})
    assert codes(fs) == {"control-cpp-extra-op"}


def test_control_detects_missing_cpp_block(tmp_path):
    cc = _PS_SERVER_CC.replace("constexpr Op kControlOps[] = {",
                               "constexpr Op kRenamed[] = {")
    fs = run_pass(tmp_path, "control", {"pkg/native/ps_server.cc": cc})
    assert "control-cpp-block-missing" in codes(fs)


def test_control_detects_decorative_cpp_block(tmp_path):
    """A kControlOps block nothing consults is worse than none: the lint
    reads it as the truth while the counter branch restates the list."""
    cc = _PS_SERVER_CC.replace(
        "constexpr bool is_control_op(int op) {\n"
        "  for (int c : kControlOps)\n"
        "    if (op == c) return true;\n"
        "  return false;\n"
        "}\n", "",
    ).replace("if (!is_control_op(op)) status += 0;  "
              "// requests counter branch\n  ", "")
    fs = run_pass(tmp_path, "control", {"pkg/native/ps_server.cc": cc})
    assert codes(fs) == {"control-cpp-unwired"}


def test_control_detects_unknown_op(tmp_path):
    wire = _WIRE_PY.replace(
        '"dsvc": frozenset({"HELLO"})',
        '"dsvc": frozenset({"HELLO", "BOGUS"})',
    )
    fs = run_pass(tmp_path, "control", {"pkg/parallel/wire.py": wire})
    assert codes(fs) == {"control-unknown-op"}


def test_control_detects_unwired_exclusion_site(tmp_path):
    """faults.py losing its CONTROL_OPS derivation re-opens the r15
    fault-index drift: op indices would count poll-cadence ops again."""
    fs = run_pass(tmp_path, "control", {
        "pkg/utils/faults.py": textwrap.dedent(
            """
            _CLIENT_KINDS = ("drop_conn", "delay")
            _KINDS = _CLIENT_KINDS + ("die",)
            """
        ),
    })
    assert codes(fs) == {"control-site-unwired"}
    assert any("faults" in f.path for f in fs)


def test_control_detects_restated_exclusion_tuple(tmp_path):
    """The literal `op not in (HELLO, STATS)` tuple is the pre-r16 shape
    the registry replaced — it must never come back."""
    dsvc = _DSVC_PY.replace(
        "counted = op not in _DSVC_CONTROL_OPS",
        "counted = op not in (DSVC_HELLO,)",
    )
    fs = run_pass(tmp_path, "control", {"pkg/data/data_service.py": dsvc})
    assert codes(fs) == {"control-restated"}


def test_control_detects_missing_registry(tmp_path):
    wire = _WIRE_PY.replace("CONTROL_OPS = {", "OTHER_OPS = {", 1)
    fs = run_pass(tmp_path, "control", {"pkg/parallel/wire.py": wire})
    assert codes(fs) == {"control-registry-missing"}


# ---------------------------------------------------------------------------
# Pass: protocol state machines (r16)
# ---------------------------------------------------------------------------


def test_protocol_detects_missing_registry(tmp_path):
    wire = _WIRE_PY.replace("WIRE_PROTOCOLS = {", "OTHER_PROTOCOLS = {", 1)
    fs = run_pass(tmp_path, "protocol", {"pkg/parallel/wire.py": wire})
    assert codes(fs) == {"proto-registry-missing"}


def test_protocol_detects_bad_rule_kind(tmp_path):
    wire = _WIRE_PY.replace('"kind": "order"', '"kind": "bogus"')
    fs = run_pass(tmp_path, "protocol", {"pkg/parallel/wire.py": wire})
    assert codes(fs) == {"proto-bad-rule"}


def test_protocol_detects_unknown_op(tmp_path):
    wire = _WIRE_PY.replace(
        '"pinged": {"PING": "pinged", "PSTORE_GET": "idle"}',
        '"pinged": {"PING": "pinged", "PSTORE_NOPE": "idle"}',
    )
    fs = run_pass(tmp_path, "protocol", {"pkg/parallel/wire.py": wire})
    assert "proto-unknown-op" in codes(fs)


def test_protocol_detects_unreachable_state(tmp_path):
    wire = _WIRE_PY.replace(
        '"pinged": {"PING": "pinged", "PSTORE_GET": "idle"},',
        '"pinged": {"PING": "pinged", "PSTORE_GET": "idle"},\n'
        '                "orphan": {"PING": "orphan"},',
    )
    fs = run_pass(tmp_path, "protocol", {"pkg/parallel/wire.py": wire})
    assert codes(fs) == {"proto-state-unreachable"}
    assert any("orphan" in f.symbol for f in fs)


def test_protocol_detects_declared_op_nobody_sends(tmp_path):
    """A transition no call-site can exercise is a state no code can
    reach — the machine promises an abort path that does not exist."""
    svc = _PS_SERVICE_PY.replace(
        "    def get(self):\n        return self.call(_PSTORE_GET, 0, 0)\n\n",
        "",
    )
    fs = run_pass(tmp_path, "protocol", {"pkg/parallel/ps_service.py": svc})
    assert codes(fs) == {"proto-op-unsent"}
    assert any("PSTORE_GET" in f.symbol for f in fs)


def test_protocol_detects_hello_not_first(tmp_path):
    """A tagged-service connect that sends a payload op before HELLO is
    the misparse-window bug the handshake rule exists for."""
    dsvc = _DSVC_PY.replace(
        "        self._attempt(DSVC_HELLO, 0)",
        "        self._attempt(DSVC_GET_BATCH, 0)\n"
        "        self._attempt(DSVC_HELLO, 0)",
    )
    assert dsvc != _DSVC_PY
    fs = run_pass(tmp_path, "protocol", {"pkg/data/data_service.py": dsvc})
    assert codes(fs) == {"proto-hello-not-first"}


def test_protocol_detects_illegal_adjacent_pair(tmp_path):
    """The no-second-BEGIN analog: two ops in one block that no state of
    the machine admits back to back."""
    svc = _PS_SERVICE_PY + textwrap.dedent(
        '''
    class Resharder:
        def double_get(self):
            self.call(_PSTORE_GET, 0, 0)
            self.call(_PSTORE_GET, 0, 0)
    '''
    )
    fs = run_pass(tmp_path, "protocol", {"pkg/parallel/ps_service.py": svc})
    assert codes(fs) == {"proto-illegal-sequence"}
    assert any("PSTORE_GET->PSTORE_GET" in f.symbol for f in fs)


def test_protocol_branch_arms_are_separate_blocks(tmp_path):
    """try-commit / except-abort is the LEGAL commit-or-abort shape: ops
    in different branch arms must never read as one illegal sequence."""
    svc = _PS_SERVICE_PY + textwrap.dedent(
        '''
    class Resharder:
        def commit_or_abort(self):
            try:
                self.call(_PSTORE_GET, 0, 0)
            except Exception:
                self.call(_PSTORE_GET, 0, 0)
    '''
    )
    fs = run_pass(tmp_path, "protocol", {"pkg/parallel/ps_service.py": svc})
    assert fs == [], [f.to_dict() for f in fs]


def test_protocol_detects_order_violation(tmp_path):
    """The sync-before-announce analog: the 'then' op reached before the
    'first' op inside one function."""
    svc = _PS_SERVICE_PY + textwrap.dedent(
        '''
    class Joiner:
        def backwards(self):
            self.call(_PSTORE_GET, 0, 0)
            self.call(_PING, 0, 0)
    '''
    )
    fs = run_pass(tmp_path, "protocol", {"pkg/parallel/ps_service.py": svc})
    assert "proto-order" in codes(fs)


# ---------------------------------------------------------------------------
# Pass: resource lifecycle (r16)
# ---------------------------------------------------------------------------


def test_lifecycle_detects_leaked_client(tmp_path):
    fs = run_pass(tmp_path, "lifecycle", {"pkg/conc/leak.py": textwrap.dedent(
        """
        def probe(addr):
            c = PSClient(addr, 1)
            c.ping()
        """
    )})
    assert codes(fs) == {"resource-leaked"}
    assert any("probe:c" in f.symbol for f in fs)


def test_lifecycle_detects_leaked_socket(tmp_path):
    fs = run_pass(tmp_path, "lifecycle", {"pkg/conc/leak.py": textwrap.dedent(
        """
        import socket


        def probe(addr):
            s = socket.create_connection(addr)
            s.sendall(b"x")
        """
    )})
    assert codes(fs) == {"resource-leaked"}


def test_lifecycle_detects_leaked_thread_and_daemon_exemption(tmp_path):
    fs = run_pass(tmp_path, "lifecycle", {"pkg/conc/leak.py": textwrap.dedent(
        """
        import threading


        def spawn(fn):
            t = threading.Thread(target=fn)
            t.start()


        def spawn_watcher(fn):
            w = threading.Thread(target=fn, daemon=True)
            w.start()
        """
    )})
    assert codes(fs) == {"resource-leaked"}
    assert [f.symbol for f in fs] == ["spawn:t"]  # daemon watcher exempt


def test_lifecycle_detects_unguarded_release(tmp_path):
    """Straight-line close() is the exact r14 leaked-heartbeat shape: an
    exception between construction and release leaks the resource."""
    fs = run_pass(tmp_path, "lifecycle", {"pkg/conc/leak.py": textwrap.dedent(
        """
        def probe(addr):
            hb = LeaseHeartbeat(addr, "m")
            hb.renew()
            hb.close()
        """
    )})
    assert codes(fs) == {"resource-release-unguarded"}


def test_lifecycle_try_finally_and_with_are_clean(tmp_path):
    fs = run_pass(tmp_path, "lifecycle", {"pkg/conc/ok.py": textwrap.dedent(
        """
        def guarded(addr):
            c = PSClient(addr, 1)
            try:
                c.ping()
            finally:
                c.close()


        def managed(addr):
            with PSClient(addr, 1) as c:
                c.ping()
        """
    )})
    assert fs == [], [f.to_dict() for f in fs]


def test_lifecycle_ownership_transfer_is_clean(tmp_path):
    """Returning, pooling, storing on self and closure hand-off all move
    ownership — the new owner's site is the one linted."""
    fs = run_pass(tmp_path, "lifecycle", {"pkg/conc/ok.py": textwrap.dedent(
        """
        def make(addr):
            c = PSClient(addr, 1)
            return c


        def pool_up(pool, addr):
            c = PSClient(addr, 1)
            pool.append(c)


        def stream(addr):
            c = PSClient(addr, 1)

            def gen():
                try:
                    yield c.ping()
                finally:
                    c.close()

            return gen()
        """
    )})
    assert fs == [], [f.to_dict() for f in fs]


def test_lifecycle_detects_unreleased_class_attr(tmp_path):
    """The leaked-heartbeat-on-self shape: a class that owns a heartbeat
    but has no teardown path for it."""
    fs = run_pass(tmp_path, "lifecycle", {"pkg/conc/svc.py": textwrap.dedent(
        """
        class Member:
            def __init__(self, addr):
                self._hb = LeaseHeartbeat(addr, "m")

            def work(self):
                return self._hb.renewals
        """
    )})
    assert codes(fs) == {"resource-attr-unreleased"}
    assert any(f.symbol == "Member._hb" for f in fs)


def test_lifecycle_released_class_attr_is_clean(tmp_path):
    fs = run_pass(tmp_path, "lifecycle", {"pkg/conc/svc.py": textwrap.dedent(
        """
        class Member:
            def __init__(self, addr):
                self._hb = LeaseHeartbeat(addr, "m")

            def close(self):
                self._hb.close()
        """
    )})
    assert fs == [], [f.to_dict() for f in fs]


# ---------------------------------------------------------------------------
# Pass: registry-manifest (r19) — atomic+durable manifest publishes.
# Scoped to files named registry.py inside the lifecycle dirs.
# ---------------------------------------------------------------------------

_REGISTRY_OK = textwrap.dedent(
    """
    import json
    import os

    def write_manifest(path, manifest):
        tmp = path + ".tmp"
        f = open(tmp, "w")
        try:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
        finally:
            f.close()
        os.replace(tmp, path)

    def publish(root, manifest):
        write_manifest(root + "/manifest.json", manifest)
        return manifest["version"]

    def publish_from_checkpoint(root, mgr):
        return publish(root, {"version": 1, "step": mgr.latest_step()})
    """
)


def test_registry_manifest_clean_writer_passes(tmp_path):
    fs = run_pass(tmp_path, "lifecycle", {"pkg/conc/registry.py": _REGISTRY_OK})
    assert fs == [], [f.to_dict() for f in fs]


def test_registry_manifest_detects_missing_fsync(tmp_path):
    """One injection: the writer renames but never fsyncs — a crash can
    surface a manifest whose bytes never reached the disk."""
    injected = _REGISTRY_OK.replace("        os.fsync(f.fileno())\n", "")
    assert "os.fsync" not in injected  # the injection really landed
    fs = run_pass(tmp_path, "lifecycle", {"pkg/conc/registry.py": injected})
    assert "registry-manifest-unfsynced" in codes(fs), [f.to_dict() for f in fs]
    # The publish path no longer reaches a COMPLIANT writer either.
    assert "registry-manifest-unrouted" in codes(fs)


def test_registry_manifest_detects_unguarded_handle(tmp_path):
    """One injection: the tmp handle is closed only on the straight-line
    path — an exception mid-dump leaks it (and on some platforms blocks
    the rename)."""
    bad = textwrap.dedent(
        """
        import json
        import os

        def write_manifest(path, manifest):
            tmp = path + ".tmp"
            f = open(tmp, "w")
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
            f.close()
            os.replace(tmp, path)

        def publish(root, manifest):
            write_manifest(root + "/manifest.json", manifest)
        """
    )
    fs = run_pass(tmp_path, "lifecycle", {"pkg/conc/registry.py": bad})
    assert "registry-manifest-unguarded" in codes(fs), [f.to_dict() for f in fs]


def test_registry_manifest_detects_unrouted_publish(tmp_path):
    """One injection: a NEW publish path writes its manifest directly,
    skipping the atomic writer entirely."""
    fs = run_pass(tmp_path, "lifecycle", {
        "pkg/conc/registry.py": _REGISTRY_OK + textwrap.dedent(
            """
            def publish_fast(root, manifest):
                with open(root + "/manifest.json", "w") as f:
                    f.write(str(manifest))
            """
        ),
    })
    assert "registry-manifest-unrouted" in codes(fs), [f.to_dict() for f in fs]
    # Only the injected path is flagged; the routed publishes stay clean.
    assert {f.symbol for f in fs} == {"publish_fast"}


def test_registry_manifest_os_open_fsync_dir_idiom_is_clean(tmp_path):
    """The directory-fsync idiom (os.open -> os.fsync -> os.close in a
    finally) is the COMPLIANT durable-rename shape, not a leak."""
    fs = run_pass(tmp_path, "lifecycle", {
        "pkg/conc/registry.py": _REGISTRY_OK + textwrap.dedent(
            """
            def fsync_dir(path):
                fd = os.open(path, os.O_RDONLY)
                try:
                    os.fsync(fd)
                finally:
                    os.close(fd)
            """
        ),
    })
    assert fs == [], [f.to_dict() for f in fs]


# ---------------------------------------------------------------------------
# --changed mode (r16): the pre-commit fast path
# ---------------------------------------------------------------------------


def test_changed_output_parity_with_full_run(tmp_path):
    """With every fixture file in the changed set, --changed must report
    EXACTLY what the full run reports — same keys, nothing dropped."""
    overrides = {
        # one wire violation + one concurrency violation
        "pkg/parallel/wire.py": _WIRE_PY.replace(
            '"PSTORE_GET": 18', '"PSTORE_GET": 19'
        ),
        "pkg/conc/worker.py": _CONC_PY.replace(
            "            x = 1\n        time.sleep(0.0)",
            "            time.sleep(0.0)\n            x = 1",
        ),
    }
    cfg = make_cfg(tmp_path, overrides)
    full = dtxlint.run_passes(cfg)
    all_files = [
        p for p in tmp_path.rglob("*") if p.is_file()
    ]
    changed = dtxlint.run_passes(cfg, changed=all_files)
    full_keys = {f.key for fs in full.values() for f in fs}
    changed_keys = {f.key for fs in changed.values() for f in fs}
    assert full_keys == changed_keys
    assert full_keys  # the injected violations actually fired


def test_changed_concurrency_runs_its_full_corpus(tmp_path):
    """The concurrency pass aggregates lock-acquisition orders across its
    whole corpus, so --changed runs it in FULL once any concurrency input
    changed: an inversion living in an UNCHANGED sibling file must still
    be reported (a per-file shrink would silently drop it)."""
    overrides = {
        "pkg/conc/a.py": "def touched():\n    return 1\n",
        "pkg/conc/b.py": textwrap.dedent(
            """
            class B:
                def fwd(self):
                    with self._x_lock:
                        with self._y_lock:
                            return 1

                def rev(self):
                    with self._y_lock:
                        with self._x_lock:
                            return 2
            """
        ),
    }
    cfg = make_cfg(tmp_path, overrides)
    results = dtxlint.run_passes(
        cfg, changed=[tmp_path / "pkg" / "conc" / "a.py"]
    )
    assert "lock-order" in {
        f.code for f in results.get("concurrency", [])
    }


def test_changed_skips_passes_whose_inputs_did_not_change(tmp_path):
    cfg = make_cfg(tmp_path)
    results = dtxlint.run_passes(
        cfg, changed=[tmp_path / "pkg" / "conc" / "worker.py"]
    )
    assert set(results) <= {"concurrency", "lifecycle"}
    results = dtxlint.run_passes(
        cfg, changed=[tmp_path / "pkg" / "parallel" / "wire.py"]
    )
    assert "wire" in results and "control" in results and \
        "protocol" in results
    assert "flag_drift" not in results


def test_cli_changed_mode_lints_only_the_diff(tmp_path, capsys):
    """End to end through git: a clean committed fixture, one violating
    edit — --changed flags it and skips stale-suppression accounting."""
    import subprocess

    cfg = make_cfg(tmp_path)
    git = ["git", "-C", str(tmp_path)]
    subprocess.run(git + ["init", "-q"], check=True)
    subprocess.run(git + ["add", "-A"], check=True)
    subprocess.run(
        git + ["-c", "user.email=t@t", "-c", "user.name=t",
               "commit", "-qm", "fixture"],
        check=True,
    )
    # Stale-by-construction suppression: --changed must NOT flag it.
    (tmp_path / "baseline.json").write_text(json.dumps({
        "suppressions": [
            {"key": "wire:op-drift:nowhere:NOPE", "reason": "stale on purpose"}
        ]
    }))
    bad = (tmp_path / "pkg" / "conc" / "worker.py")
    bad.write_text(_CONC_PY.replace(
        "            x = 1\n        time.sleep(0.0)",
        "            time.sleep(0.0)\n            x = 1",
    ))
    # The CLI default() layout expects the real repo shape — point the
    # config fields at the fixture via a tiny shim around run_passes.
    from tools.dtxlint.__main__ import changed_files

    changed = changed_files(str(tmp_path), "HEAD")
    rels = [os.path.relpath(c, tmp_path) for c in changed]
    # The edited file AND the untracked baseline both count as changed
    # (untracked files are part of a pre-commit diff's blast radius).
    assert "pkg/conc/worker.py" in rels and "baseline.json" in rels
    results = dtxlint.run_passes(cfg, changed=[Path(c) for c in changed])
    keys = {f.code for fs in results.values() for f in fs}
    assert keys == {"blocking-under-lock"}
    # Stale accounting is the full run's job: apply_baseline + the CLI's
    # changed-mode stale reset.
    baseline = load_baseline(tmp_path / "baseline.json")
    active, suppressed, stale = apply_baseline(results, baseline)
    assert stale  # the full-run path WOULD flag it...
    # ...and the CLI drops it under --changed (pinned by the flag's
    # contract; exercised against the real repo in the CLI tests above).


def test_dtxlint_step_emits_gated_metric():
    """The shim's single JSON line carries the metric + seconds, on top of
    the full --json document shape.  The lint runs inside tier-1 on every
    PR, so a pass whose cost quietly explodes (an accidentally quadratic AST
    walk) taxes every run: all 7 passes took 2.5 s on the dev box at r16, and
    ten times a 30 s budget for slow hosts is where this fails."""
    import subprocess

    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "dtxlint_step.py")],
        capture_output=True, text=True, cwd=ROOT, timeout=240,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout.strip().splitlines()[-1])
    assert doc["metric"] == "dtxlint"
    assert doc["ok"] is True
    assert 0 < doc["seconds"] < 10 * 30.0
    assert doc["schema_version"] == dtxlint.JSON_SCHEMA_VERSION
