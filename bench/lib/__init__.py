"""The benchmark's yardstick: peaks, trace reduction, counts, weights, reference."""
