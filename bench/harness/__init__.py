"""The benchmark's own machinery: traffic, model kinds (harness.kinds,
whose files in bench/kinds/ hold each architecture's weights, plain
reference and counts), the served run and its window, the comparison
that decides `correct`, peaks and the least time, and the reductions of
the profiler trace and of the program's own spans and counters.
Nothing here is imported by the program; the program is imported from
``src/``."""
