"""The metadata the program sets on its host spans, read back from the trace.

``trace.Span`` keeps a host span's name and times.  What the program set
on its annotation (``TraceAnnotation`` keyword arguments,
``set_metadata``) is kept in the profile as the event's stats; ``spans``
reads them back for the host spans of one name.  By default it reads the
trace that ``cell.run`` has the profiler write for a traced window, which
is still on disk while the per-layer readers run.
"""
from __future__ import annotations

import glob
import os
import warnings

from bench.harness import trace
from bench.harness.cell import CHECKOUT

TRACE_DIR = CHECKOUT / ".bench_tmp" / "trace"  # the profiler's directory in cell.run


def spans(name: str, directory=None) -> list[tuple[trace.Span, dict]]:
    """Each host span called ``name``, with its stats, in time order; none
    where the directory (``TRACE_DIR`` if not given) holds no trace."""
    from jax.profiler import ProfileData

    directory = TRACE_DIR if directory is None else directory
    paths = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        return []
    out = []
    with warnings.catch_warnings():
        # Reading the stats of an event warns once per event type.
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(paths[0]).planes:
            if plane.name.startswith("/host:"):
                out.extend((trace._span(e), trace._stats(e))
                           for line in plane.lines for e in line.events if e.name == name)
    return sorted(out, key=lambda pair: pair[0].start)
