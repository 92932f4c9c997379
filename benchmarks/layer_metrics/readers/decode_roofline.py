"""Roofline share of the batched decode step, in %.

Least bytes one step must read, as the configuration's family counts them
(``families/<model>/serve.py`` ``decode_step_bytes``: every parameter the
step uses once, in the type the configuration holds them in, plus the state
the seated sessions have written so far), over the published HBM bandwidth;
divided by the median device time of the step's launches.  Memory-bound: at
8 rows the products are negligible beside the bytes.
"""

import statistics

from benchmarks.harness import flops, manifest, trace


def read(evidence, *, module):
    tr = evidence.get("trace")
    if not tr:
        return None
    events = trace.module_events(tr, module)
    if len(events) < 3:
        return None
    step_s = statistics.median(e - s for s, e, _n in events)
    config = evidence["cell"].config
    slots = evidence["counters"]["end"]["decode_slots"]
    rows = flops.mean_cache_rows(
        evidence["records"], evidence["schedule"]["requests"],
        evidence["w1"] - evidence["trace_s"], evidence["w1"],
    )
    least = manifest.family(config["model"], "serve").decode_step_bytes(
        config, slots=slots, cache_rows=rows)
    peak = evidence["peaks"]["hbm_bytes_per_s"]
    return 100.0 * (least / peak) / step_s
