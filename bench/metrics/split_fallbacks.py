"""Forward levels whose polyphase split took strided slices instead of
the split kernel, from the program's counter
``repro_split_path_total{path, level}`` (counted once per level when a
forward executable is traced, in the warm-up).  None where the program
has no such counter."""


def read(ctx):
    from repro import telemetry
    counter = telemetry.REGISTRY.get("repro_split_path_total")
    rows = counter.series() if counter is not None else []
    if not rows:
        return None
    return len({r["labels"]["level"] for r in rows
                if r["labels"]["path"] == "slices" and r["value"] > 0})
