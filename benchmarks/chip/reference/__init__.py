"""Plain float32 reference for the chip benchmark's correctness check.

Imports nothing of ``repro``: each tower block is written out here as the
program defines it (``<block>.py``; ``tower.py`` lists what a block
exports), the weights of every tower are drawn from the seed by the same
documented initialisation (``weights.py``), and the int4 rule and the store
scan are plain numpy.
"""
