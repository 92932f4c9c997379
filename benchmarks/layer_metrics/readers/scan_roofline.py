"""Memory-roofline share of the selective-scan kernel, in %.

Least bytes one call must move for a chunk of ``chunk`` tokens, as the
configuration's family counts them from shapes (``scan_chunk_bytes``: the
inputs and the carried state read once, the outputs and the state written
once), over the published HBM bandwidth; divided by the mean device time of
the operations whose name holds ``op``.  The kernel is bound by the vector
unit, for which no peak is published, so this share is a FLOOR on how good
the kernel is, not its distance from what the chip could do.
"""

from benchmarks.harness import manifest, trace


def read(evidence, *, op, chunk):
    tr = evidence.get("trace")
    if not tr:
        return None
    seconds, n = trace.op_seconds(tr, op)
    config = evidence["cell"].config
    count = getattr(manifest.family(config["model"], "serve"), "scan_chunk_bytes", None)
    if not n or count is None:
        return None
    least_s = count(config, chunk) / evidence["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (seconds / n)
