"""The chip benchmark of the CIM reproduction: one cell, one run.

Everything under this directory is the yardstick: traffic, weights and
inputs made from the seed, the plain references that decide
``correct``, the work counts, the table of peaks and the reduction from
profiler traces to metrics. The program under test is reached only
through the entry points each driver names. See ``run.py``.
"""
