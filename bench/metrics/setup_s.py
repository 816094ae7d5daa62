"""Seconds from the start of the process to the end of the warm-up:
imports, planning, compiling, making the data and the warm-up calls."""


def read(r):
    return r.setup_s
