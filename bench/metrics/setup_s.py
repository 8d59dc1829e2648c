"""Seconds from the process's start to the start of the load: loading,
compiling or loading the plans from the compile cache, operands, and
the warm-up of every batch width."""
LAYER = None
UNIT = "s"
MOVES = None


def read(rec):
    return rec["setup_s"]
