"""On-chip benchmark of the ProbGraph mining path."""
