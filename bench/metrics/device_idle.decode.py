"""Share of the window in which no operation ran on the device (1 − the
busy union over the window, from the trace)."""


def read(ctx, summary, res):
    return 100.0 * summary.idle_share
