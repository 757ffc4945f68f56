"""The chip benchmark's own library: traffic, weights, reference, checks,
FLOP counts, peaks and trace reduction. It imports nothing of the program
under test; only ``run.py`` and the tests do."""
