"""Per-layer metric readers: ``read(ctx, **args) -> float | None``.

One small module per kind of reading; ``metrics/<name>.json`` names the
module and its arguments.  A reader that finds nothing to read returns
None, and the harness leaves the metric out of the line.
"""
