"""The chip benchmark's yardstick: peaks, work counts, traffic, the plain
reference, the comparison that decides `correct`, and the trace reduction.

Nothing here imports the program under test (`src/repro`) except the
runners (`bench/<kind>.py`, found by the traffic's kind), which run it, and
the program-side functions of the architectures (`bench.arch`).
"""
