"""The benchmark's own library: the yardstick that later changes to the
program cannot move.

Everything here reads the system under test only through its entry
points (the step builders, their state and the trace they leave).  The
reference in :mod:`reference` imports nothing of the program.
"""
