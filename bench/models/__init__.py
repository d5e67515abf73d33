"""Per-family weights, plain references and FLOP counts."""
