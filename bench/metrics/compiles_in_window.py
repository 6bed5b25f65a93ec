"""Compilation: programs compiled or loaded inside the measured windows,
counted by a ``jax.monitoring`` listener on backend-compile events."""


def read(ctx):
    return ctx.compiles_in_window
