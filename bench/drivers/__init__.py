"""General traffic drivers, one per kind, found by name."""
