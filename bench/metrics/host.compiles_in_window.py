"""host.compiles_in_window: executables the facade built inside the window."""


def read(ctx):
    """The program's runtime.executable.compile counter, window delta."""
    return ctx.compiles_in_window
