"""One reader per metric: ``read(run)`` returns a number, or None when the
run holds nothing to read.  The harness finds the reader of a metric named
``base.suffix`` in ``<base>.py``."""
