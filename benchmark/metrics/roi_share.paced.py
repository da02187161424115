"""Share of the host codecs' decodes that read only an /extract's window of
the frame: the codecs' `roi_decodes` over their `decodes`, untraced part of
the window (paced cells). None where the program lacks the counter."""


def read(ctx):
    if not ctx.profile:
        return None
    c0, c1 = (h.get("codecs", {}) for h in (ctx.health_start, ctx.profile["health_a"]))
    if not all(k in c for c in (c0, c1) for k in ("roi_decodes", "decodes")):
        return None
    n = c1["decodes"] - c0["decodes"]
    if n <= 0:
        return None
    return (c1["roi_decodes"] - c0["roi_decodes"]) / n
