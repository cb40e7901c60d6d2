"""One-off chip tools (trace probe, capacity sweep, control readings)."""
