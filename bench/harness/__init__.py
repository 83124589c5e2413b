"""The benchmark's yardstick: traffic, weights, the plain reference, the
trace reduction, peaks and operation counts.  Nothing here imports the
program under test; ``bench/run.py`` joins the two."""
