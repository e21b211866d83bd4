"""The benchmark's harness: data-driven cells, general traffic drivers,
trace reduction, peaks, cost counts and plain references."""
