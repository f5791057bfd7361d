"""Set-up: backend compiles that happened inside the measured window. A
program missing from the warm-up shows here."""


def read(ctx):
    return float(ctx.compiles_in_window)
