"""The benchmark's shared code: what later cells measure with and may not
change (registry, traffic generator, trace reduction, peaks, operation
counts, plain references)."""
