"""device.start_wait_ms: launch to the first op of the call on the chips."""

from bench import stages


def read(ctx):
    """Per traced call, from the start of the program's coded.launch span to
    the first op of the module it launched, on the chip that starts last;
    the mean.  Nothing without the program's spans."""
    return stages.start_wait_ms(ctx)
