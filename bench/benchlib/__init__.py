"""The benchmark's own library: the yardstick that later changes to the
program cannot move (traffic, weights, plain references, trace reduction,
peaks and operation counts)."""
