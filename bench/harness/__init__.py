"""The benchmark's own code: deployments, traffic, the plain reference,
the comparison that decides `correct`, and the trace reduction.

Nothing here imports the program except `drive.py`, which the loops
(`bench/loops/`) use to call the system under test, and `faults.py`,
which breaks the timed path for the readings and tests of `correct`.
"""
