"""The chip benchmark's harness: cells, data, the system under test, the
plain reference and the trace reduction. ``run.py`` is the entry point."""
