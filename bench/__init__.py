"""The on-chip benchmark: harness, reference, trace reduction, cell data."""
