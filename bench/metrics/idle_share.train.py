"""Share of the traced training window in which no operation ran on the
device: the trainer's host work between steps (the data iterator, the
sync on each step's loss)."""


def read(ctx):
    t = ctx["trace"]
    if t.window_s <= 0 or not t.devices:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)
