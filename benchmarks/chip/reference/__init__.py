"""Plain float32 reference for the chip benchmark's correctness check.

Imports nothing of ``repro``: the tower block is written out here as the
program defines it, its weights are drawn from the seed by the same
documented initialisation, and the int4 rule and the store scan are plain
numpy.
"""
