"""The program's own host spans and request stamps of a traced window, for
the per-layer readers that read them.

The program records them (`repro.core.tracing.HOST_SPANS`) while a JAX
profile is being captured, which in a `--trace 1` run is the window, and
keeps them in memory; they are read here after the window, in the same
process, and left in place. The window is cut from the recorder alone and
`Reading.window_s`: it ends where the last recorded span ended (the window
closes as its last engine call returns) and lasts `Reading.window_s`. A
program without the recorder, or a run in which it recorded nothing or
lost records off its bounded buffers, gives None, and the readers then
print nothing.
"""
from __future__ import annotations

import statistics
from typing import NamedTuple, Optional


class Window(NamedTuple):
    end: float           # host clock at the window's end
    spans: list          # repro.core.tracing.HostSpan, closed in the window
    stamps: list         # repro.core.tracing.HostStamp, in the window


def window(r) -> Optional[Window]:
    try:
        from repro.core.tracing import HOST_SPANS
    except ImportError:                  # a program without host spans
        return None
    if not HOST_SPANS.spans or HOST_SPANS.dropped:
        return None
    hi = max(s.end for s in HOST_SPANS.spans)
    lo = hi - r.window_s
    spans = [s for s in HOST_SPANS.spans if lo <= s.start and s.end <= hi]
    stamps = [s for s in HOST_SPANS.stamps if lo <= s.t <= hi]
    if not spans:
        return None
    return Window(hi, spans, stamps)


def median_ms(r, name: str) -> Optional[float]:
    """Median duration of the window's spans named `name`, in ms."""
    w = window(r)
    t = [s.duration for s in w.spans if s.name == name] if w else []
    return statistics.median(t) * 1e3 if t else None
