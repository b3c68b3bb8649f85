"""The on-chip benchmark of the SNN serving path (see ``bench/run.py``).

Everything that measures lives here, apart from the program under test:
traffic generation, the plain float64 reference and its comparison, the
reduction from profiler traces to metrics, the roofline's work function and
the table of peaks.  The program (``src/repro``) is imported only as the
system under test.
"""
