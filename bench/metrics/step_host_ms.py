"""Host time per decode step, in ms: over the ``serve.step`` spans in
the traced window that hold a ``serve.decode``, the mean of the step's
length less its decode's (the decode program's launch and its wait on
the device). What is left is admission, the decode's inputs, sampling
and the per-row bookkeeping."""
from bench import spans


def read(ctx):
    if ctx.trace is None:
        return None
    by_name = spans.program_spans(ctx.trace)
    decodes = by_name.get("serve.decode", [])
    host = []
    for step in by_name.get("serve.step", []):
        held = spans.inside(step, decodes)
        if held:
            host.append(step.dur - sum(e.dur for e in held))
    if not host:
        return None
    return sum(host) / len(host) / 1e6
