"""The benchmark: cells, drivers, reference and trace reduction."""
