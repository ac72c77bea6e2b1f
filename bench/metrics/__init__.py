"""One reader per metric: ``read(ctx)`` returns the number, or None when
the run holds nothing to read it from (the metric is then left out)."""
