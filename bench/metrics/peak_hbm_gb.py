"""peak_hbm_gb: the highest peak of device memory over the cell's chips."""


def read(ctx):
    """Per chip the allocator's peak of live buffers plus the peak it
    reserved for executables' temporaries, read after the window; in GB."""
    return ctx.peak_bytes / 1e9 if ctx.peak_bytes else None
