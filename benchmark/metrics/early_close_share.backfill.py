"""Share of the executor's chunks closed before the formation cap because
no request of their routes was on its way: the executor's `early_closes`
over its `batches`, untraced part of the window (backfill cells). None where the
program lacks the counter."""


def read(ctx):
    if not ctx.profile:
        return None
    e0, e1 = (h.get("executor", {}) for h in (ctx.health_start, ctx.profile["health_a"]))
    if not all(k in e for e in (e0, e1) for k in ("early_closes", "batches")):
        return None
    n = e1["batches"] - e0["batches"]
    if n <= 0:
        return None
    return (e1["early_closes"] - e0["early_closes"]) / n
