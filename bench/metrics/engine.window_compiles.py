"""Backend compiles (JAX monitoring events) inside the measured window:
a resize of the pair budget, or any shape the warm-up missed, shows
here. It should read 0."""


def read(ctx):
    return ctx["compiles_in_window"]
