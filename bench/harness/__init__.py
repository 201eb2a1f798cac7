"""The benchmark's harness: spec resolution, traffic, the system under test,
the measured window, work counts, trace reduction and the comparison that
decides ``correct``."""
