"""The chip benchmark's yardstick: traffic generation, end-to-end arithmetic,
trace reduction, peaks, operation counts and the plain reference.

Nothing in this package imports the program under test except
``chipbench.harness``, which builds and drives the served path.
"""
