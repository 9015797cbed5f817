"""Compile: backend compiles that ``jax.monitoring`` reports inside the
measured window — XLA compiles and persistent-cache loads alike.  It
should read 0: every shape the window uses is warmed up in set-up."""


def read(run):
    return run.compiles_in_window
