"""The benchmark's own machinery: traffic, weights, the served run and
its window, the plain reference, the comparison that decides `correct`,
FLOP/byte counts, peaks and the profiler-trace reduction.  Nothing here
is imported by the program; the program is imported from ``src/``."""
