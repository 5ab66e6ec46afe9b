"""Share of the traced serving window in which no operation ran on the
device, %: 1 - (union of the device op intervals) / window."""


def read(ctx):
    tr = ctx["trace"]
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
