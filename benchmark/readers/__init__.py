"""Readers: each takes one per-layer metric from the trace, the driver's
host records or both; found by the name a metric's file gives. A reader
that finds nothing to read returns None."""
