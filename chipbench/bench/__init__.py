"""The chip benchmark's yardstick: peaks, work counts, traffic, the plain
reference, the comparison that decides `correct`, and the trace reduction.

Nothing here imports the program under test (`src/repro`) except the
runner, `bench.train`, which runs it.
"""
