"""Milliseconds the interpreter spent in full (generation 2) collections
during the window, from the ``gc.gen2`` spans; 0 where none ran.  Each
one stops the host, and with it every order due meanwhile."""


def read(ctx, metric):
    span = ctx.trace["spans"].get("gc.gen2")
    return 1e3 * span["total_s"] if span else 0.0
