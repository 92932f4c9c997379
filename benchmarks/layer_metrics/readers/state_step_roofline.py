"""Memory-roofline share of the state-step kernel (Mamba-2's one-token
update of every live slot's state, in place), in %.

Least bytes one call must move, as the configuration's family counts them
from shapes (``state_step_bytes``: each LIVE slot's state read once and
written once, its inputs and outputs), over the published HBM bandwidth;
divided by the mean device time of the operations whose name holds ``op``.
How many slots a step found live is the tokens it emitted, and BOTH sides
are taken over the traced stretch: the tokens the clients received in it
(their stamps) over the launches of ``module`` in the trace.  The window's
mean will not do: with some six rows live the stretch's mean lies up to a
third off it either way, and the share with it (PERF.md section 6, PR 46).
Nothing where the family has no such count or the trace no such operation.
"""

from benchmarks.harness import manifest, stats, trace


def read(evidence, *, op, module):
    tr, records = evidence.get("trace"), evidence.get("records")
    if not tr or records is None:
        return None
    config = evidence["cell"].config
    count = getattr(manifest.family(config["model"], "serve"), "state_step_bytes", None)
    seconds, n = trace.op_seconds(tr, op)
    t0, t1 = trace.window_of(tr)
    launches = trace.clip(trace.module_events(tr, module), t0, t1)
    if not n or not launches or count is None:
        return None
    # The traced window ends where the measured one does, on the host's clock.
    tokens = stats.tokens_in_window(
        [t for r in records for t in r["times"]], evidence["w1"] - (t1 - t0), evidence["w1"])
    least_s = count(config, tokens / len(launches)) / evidence["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (seconds / n)
