"""Mean time from a decode's end to the end of its step, in ms: the
``serve.sample`` spans in the traced window (the pool's lengths
advanced, the next tokens sampled and copied to the host, finished
requests retired)."""
from bench import spans


def read(ctx):
    if ctx.trace is None:
        return None
    evs = spans.program_spans(ctx.trace).get("serve.sample")
    if not evs:
        return None
    return sum(e.dur for e in evs) / len(evs) / 1e6
