"""Mean rows per decode step: the ``live_rows`` stat of the
``serve.decode`` spans in the traced window, the batch over which each
step's fixed cost is spread."""
from bench import spans


def read(ctx):
    if ctx.trace is None:
        return None
    evs = spans.program_spans(ctx.trace).get("serve.decode", [])
    rows = [float(e.stats["live_rows"]) for e in evs if "live_rows" in e.stats]
    if not rows:
        return None
    return sum(rows) / len(rows)
