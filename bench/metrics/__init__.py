"""Per-layer metric readers: `<name>.py` holds `read(ctx)`, which returns
the metric from the window's records, counters or trace, or None where
there is nothing to read (the run then leaves the metric out)."""
