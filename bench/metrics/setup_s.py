"""setup_s: seconds from process start until the window opens (loading, compiling,
warming up)."""


def read(ctx):
    return ctx.setup_s
