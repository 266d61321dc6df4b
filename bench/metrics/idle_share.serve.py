"""Share of the traced serving window in which no operation ran on the
device: the engine's host path (scheduling, sampling, telemetry) and
the syncs between steps."""


def read(ctx):
    t = ctx["trace"]
    if t.window_s <= 0 or not t.devices:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)
