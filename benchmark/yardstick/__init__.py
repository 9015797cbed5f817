"""The benchmark's own code: traffic generation, the plain reference,
the window loop, the statistics and the trace reduction.

Only :mod:`yardstick.myth` drives the system under test.  The
reference (:mod:`yardstick.evm`, :mod:`yardstick.reference`) and the
generators (:mod:`yardstick.contracts`) import nothing of the program.
"""
