"""Programs prepared in the server during the window: every lowering of a
new shape, whether it then compiles or loads from the compile cache."""


def read(ctx):
    return len(ctx["compiles"])
