"""The benchmark's own yardstick: data and weights from the seed, the
FLOP/byte count, the peaks table, the trace reduction, the window
arithmetic, the metric readers and the plain reference.  Nothing here
imports the program under test."""
