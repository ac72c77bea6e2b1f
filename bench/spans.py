"""The program's own spans in a reduced trace: the ``serve.*`` spans a
scheduler with telemetry on opens through ``Telemetry.span``, on the
thread that opened the ``bench.window`` annotation. The readers of
``admit_ms``, ``step_host_ms``, ``sample_ms`` and ``decode_rows_mean``
share them."""
from __future__ import annotations

from collections import defaultdict

from bench.trace import Ev, Trace

PROGRAM_STEP = "serve.step"


def program_spans(tr: Trace) -> dict:
    """The program's spans that lie wholly inside the window, by name, in
    time order. Empty when the window holds no ``serve.step`` span: a
    program that does not name its steps is not read."""
    a, b = tr.window
    out = defaultdict(list)
    for e in tr.host:
        if e.name.startswith("serve.") and e.start >= a and e.end <= b:
            out[e.name].append(e)
    if PROGRAM_STEP not in out:
        return {}
    for evs in out.values():
        evs.sort(key=lambda e: e.start)
    return dict(out)


def inside(outer: Ev, evs) -> list:
    """Those of ``evs`` that lie within ``outer``."""
    return [e for e in evs if e.start >= outer.start and e.end <= outer.end]
