"""The benchmark's own library: the cell's files found by name, operands
from the seed, the closed-loop load, the window, the trace reduction and
the comparison that decides ``correct``. It imports nothing of the
program except in ``load.py``, which drives the system under test."""
