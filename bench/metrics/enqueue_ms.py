"""Mean duration of the program's own ``execute.forward`` /
``execute.inverse`` spans that began in the window: plan execution up
to the enqueue, timed inside the program (``dispatch_ms`` adds the
entry point and plan lookup around it).  Read from the span ring,
which keeps the newest spans (``$REPRO_TELEMETRY_RING``, 4096 by
default)."""
SPANS = ("execute.forward", "execute.inverse")


def read(ctx):
    if ctx.setup_s is None:
        return None
    from repro import telemetry
    start = ctx.t_start + ctx.setup_s
    spans = [r.dur_s for r in telemetry.TRACER.records()
             if r.name in SPANS and r.start_s >= start]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
