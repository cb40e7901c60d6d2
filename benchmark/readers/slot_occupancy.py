"""Mean share of the engine's slots in use, in %, over the steps taken
inside the window (the driver reads ``scheduler.active_slots`` after each
``step()``)."""


def read(ctx):
    host = ctx["host"]
    ticks = [t for t in host.get("ticks", ()) if 0.0 <= t[0] < ctx["cell_seconds"]]
    if not ticks or not host.get("max_slots"):
        return None
    return 100.0 * sum(t[2] for t in ticks) / len(ticks) / host["max_slots"]
