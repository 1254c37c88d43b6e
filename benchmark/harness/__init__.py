"""The benchmark's yardstick: traffic generation, phases and spans of a run,
the comparison that decides ``correct``, FLOP counts, peaks and the trace
reduction. Nothing here imports the program; ``benchmark/drivers`` does."""
