"""Stream kinds of the load generator, one module each (``STREAM``),
found by file name."""
