"""Shared pieces of the chip benchmark: window drivers, traffic generator,
trace reduction, operation counts, peaks and the plain reference."""
