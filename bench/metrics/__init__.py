"""One reader per metric: ``read(run)`` returns the number or None."""
