"""chipbench: the benchmark of foremast-tpu on the chip.

One command runs one cell (a configuration under a traffic mix) once in a
new process and prints one JSON line; see README.md in this directory.
Everything the yardstick needs lives here: the seeded generators, the
plain reference, the comparison that decides `correct`, the reduction of
a profiler trace to metrics, the table of peaks and the byte reckonings.
From `foremast_tpu` it takes only the system under test and its spans,
counters and program names.
"""
