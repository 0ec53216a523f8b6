"""The on-chip benchmark: cells, traffic, references and metric readers."""
