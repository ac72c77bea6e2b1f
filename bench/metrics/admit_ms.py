"""Mean time to admit one request, in ms: the ``serve.admit_request``
spans in the traced window. Each covers what the scheduler does for a
request it admits: gather a prefix hit, dispatch the prefill, install
the prefill's cache into the pool (``write_prefill``) and sample the
first token."""
from bench import spans


def read(ctx):
    if ctx.trace is None:
        return None
    evs = spans.program_spans(ctx.trace).get("serve.admit_request")
    if not evs:
        return None
    return sum(e.dur for e in evs) / len(evs) / 1e6
