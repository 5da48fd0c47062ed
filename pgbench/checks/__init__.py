"""Output checks, one module per traffic ``check`` kind, found by name."""
