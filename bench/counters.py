"""Delta reader over the program's ``runtime.executable.compile`` counter.

The coded-matmul facade counts each executable it builds under
``runtime.executable.compile`` (``repro.obs``).  The counter is dead while
observability is off, so constructing a watch turns it on; totals are read
across all label sets, so a per-kind split cannot hide a build.
"""
from __future__ import annotations

from repro import obs

__all__ = ["CompileWatch"]


class CompileWatch:
    """Executable builds since the last ``mark``."""

    COUNTER = "runtime.executable.compile"

    def __init__(self):
        obs.enable()
        self._mark = self.compiles()

    def compiles(self) -> int:
        """Total executable builds so far (all kinds)."""
        return int(obs.session().registry.total(self.COUNTER))

    def mark(self) -> int:
        """Re-baseline: ``delta`` counts from here."""
        self._mark = self.compiles()
        return self._mark

    def delta(self) -> int:
        """Executable builds since the last ``mark``."""
        return self.compiles() - self._mark
