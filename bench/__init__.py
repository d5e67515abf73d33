"""On-chip serving benchmark: cells, traffic, metrics and the reference."""
