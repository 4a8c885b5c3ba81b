"""Generic harness pieces: registry, chip, trace, peaks, statistics."""
