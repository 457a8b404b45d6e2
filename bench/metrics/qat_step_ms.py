"""Device time of one client QAT step: the device time of the compiled
programs that ran once for every batch the clients drew in the window (a
count the harness keeps), over that count."""


def read(ctx, summary, res):
    n = ctx.facts["batches"]
    t, runs = summary.programs_run(n)
    return 1e3 * t / n if runs else None
