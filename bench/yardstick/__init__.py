"""The benchmark's yardstick: everything that turns a run into numbers.

Nothing here imports the program under test (``src/repro``).  Later
changes to the program are measured by these files as they stand.
"""
