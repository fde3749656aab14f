"""The benchmark's own yardstick, free of any model family: the peaks table
and the roofline's bound, the trace reduction, the span record's readers,
the window arithmetic, the metric readers, and the fit-following and the
comparison of the plain reference.  Rows, weights, the work count and the
plain forward are the configuration's family's (``families/``).  Nothing
here imports the program under test."""
