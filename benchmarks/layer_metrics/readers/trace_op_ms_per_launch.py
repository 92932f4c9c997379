"""Device time, in ms, of the operations whose name holds ``op``, per launch
of the program whose name holds ``module``, over the traced window: what a
kernel that a program calls many times (once a layer) costs a launch."""

from benchmarks.harness import trace


def read(evidence, *, op, module):
    tr = evidence.get("trace")
    if not tr:
        return None
    seconds, n = trace.op_seconds(tr, op)
    w0, w1 = trace.window_of(tr) if n else (0.0, 0.0)
    launches = trace.clip(trace.module_events(tr, module), w0, w1)
    if not n or not launches:
        return None
    return 1e3 * seconds / len(launches)
