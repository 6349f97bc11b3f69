"""The benchmark's own library: the yardstick that later changes to the
program cannot move (traffic generation, trace reduction, work counts,
peaks, weights, the correctness comparison)."""
