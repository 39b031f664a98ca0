"""Host seconds the program spent lowering and compiling its plan
executors (``repro_executor_compile_seconds_total``, persistent-cache
loads included).  Each executor compiles once per argument signature,
in the warm-up, so the total read after the window is its value at the
window's start."""


def read(ctx):
    from repro import telemetry
    counter = telemetry.REGISTRY.get("repro_executor_compile_seconds_total")
    rows = counter.series() if counter is not None else []
    if not rows:
        return None
    return sum(r["value"] for r in rows)
