"""host.panel_ms: host time per call in the decode-panel lookup and upload."""

from bench import stages


def read(ctx):
    """The program's coded.panel span, mean over the traced calls; nothing
    without it."""
    return stages.span_ms(ctx, "coded.panel")
