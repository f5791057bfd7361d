"""Set-up: seconds of backend compilation before the window opened
(``jax.monitoring`` compile events). Near 0 once the compile cache holds
every program."""


def read(ctx):
    return ctx.compile_setup_s
