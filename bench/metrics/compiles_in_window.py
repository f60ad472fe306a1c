"""Backend compilations (or persistent-cache loads) inside the window;
every program shape should have been compiled in set-up."""


def read(ctx):
    return ctx.compiles
