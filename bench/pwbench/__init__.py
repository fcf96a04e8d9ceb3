"""The benchmark's own code: traffic, weights, reference, trace reduction
and the comparison that decides ``correct``. Nothing here is imported by
the program; from the program it takes only the system under test."""
