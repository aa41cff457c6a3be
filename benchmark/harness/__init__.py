"""General parts of the benchmark: what no single cell, configuration,
traffic mix or metric owns."""
