"""Set-up: from process start to the window's start (loading, weights,
compilation, the program's warm-up, and the first wave: every client's
first prefill and first decode step)."""


def read(ctx):
    return ctx.setup_s
