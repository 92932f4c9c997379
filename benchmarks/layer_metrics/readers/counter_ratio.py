"""100 x delta(num) / (delta(den) x scale), from counters read at the
window's two ends.  ``scale`` names a counter whose value multiplies the
denominator (the slot count)."""


def read(evidence, *, num, den, scale=None):
    c = evidence.get("counters")
    if not c or num not in c["end"] or den not in c["end"]:
        return None
    d_num = c["end"][num] - c["start"][num]
    d_den = c["end"][den] - c["start"][den]
    if scale is not None:
        d_den *= c["end"][scale]
    return 100.0 * d_num / d_den if d_den > 0 else None
