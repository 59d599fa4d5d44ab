"""Entry points the traffic files name: one module per entry, found by
the ``entry`` key of a traffic file."""
