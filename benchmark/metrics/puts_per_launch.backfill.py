"""Arrays one executor launch puts host->device (pixels, packed params, any
wide param): the executor's `launch_puts` over its `launches`, untraced part
of the window (backfill cells). None where the program lacks the counter."""


def read(ctx):
    if not ctx.profile:
        return None
    e0, e1 = (h.get("executor", {}) for h in (ctx.health_start, ctx.profile["health_a"]))
    if not all(k in e for e in (e0, e1) for k in ("launch_puts", "launches")):
        return None
    n = e1["launches"] - e0["launches"]
    if n <= 0:
        return None
    return (e1["launch_puts"] - e0["launch_puts"]) / n
