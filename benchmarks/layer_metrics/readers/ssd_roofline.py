"""Roofline share of the chunked-recurrence kernel (Mamba-2's state-space
duality form over a prefill chunk), in %.

The least time one call could take - the larger of its operations over the
published bf16 peak and the bytes it cannot keep off HBM over the published
bandwidth, as the configuration's family counts them from shapes
(``ssd_chunk_flops``: the three products a head a block and ``B C^T`` a
group; ``ssd_chunk_bytes``: the carried state each way - the call's other
operands are values of the chunk's own program, which the compiler holds in
VMEM) - over the mean device time of the operations whose name holds ``op``.
The operations' bound holds wherever the compiler puts an operand, and at
512 positions it is the larger of the two (17.0 us against 10.2).  The engine
dispatches a chunk at more than one width and the operations are linear in
it, so the call is taken at the MEAN width of the window: the model counts its calls and the positions they were dispatched at
on the device (``calls``, ``positions``: ``server.stats()`` keys read at the
window's two ends).  Nothing where the program has no such counter, the
family no such count, or the trace no such operation.
"""

from benchmarks.harness import manifest, trace


def read(evidence, *, op, calls, positions):
    tr, c = evidence.get("trace"), evidence.get("counters")
    if not tr or not c:
        return None
    if any(k not in c[end] for k in (calls, positions) for end in ("start", "end")):
        return None
    n_calls = c["end"][calls] - c["start"][calls]
    config = evidence["cell"].config
    family = manifest.family(config["model"], "serve")
    count_bytes = getattr(family, "ssd_chunk_bytes", None)
    count_flops = getattr(family, "ssd_chunk_flops", None)
    seconds, n = trace.op_seconds(tr, op)
    if not n or n_calls <= 0 or count_bytes is None or count_flops is None:
        return None
    chunk = (c["end"][positions] - c["start"][positions]) / n_calls
    peaks = evidence["peaks"]
    least_s = max(count_bytes(config, chunk) / peaks["hbm_bytes_per_s"],
                  count_flops(config, chunk) / peaks["bf16_flops_per_s"])
    return 100.0 * least_s / (seconds / n)
