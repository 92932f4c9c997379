"""Peak device memory in GB on the fullest chip: the allocator's peak, or
where the run holds the compiled step, its arguments plus temporaries,
whichever is larger (the allocator's peak leaves temporaries out)."""


def read(evidence):
    peaks = [m.get("peak_bytes_in_use", 0) for m in evidence.get("memory") or []]
    peaks.append(evidence.get("compiled_bytes") or 0)
    return max(peaks) / 1e9 if max(peaks) > 0 else None
