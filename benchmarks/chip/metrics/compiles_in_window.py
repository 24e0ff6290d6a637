"""XLA backend compiles between the window's start and the last reply
(``CompileClock``); set-up warms every shape, so this should read 0."""


def read(ctx):
    return float(ctx["compiles_in_window"])
