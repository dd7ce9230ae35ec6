"""Compile: ``progcache.xla_compile_count()`` after the window minus before
it.  Anything but 0 means the warm-up fit missed a shape."""


def read(ctx):
    return ctx.window_compiles
