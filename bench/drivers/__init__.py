"""General traffic drivers, one per kind of traffic file."""
