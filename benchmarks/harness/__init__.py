"""The benchmark's own code: everything the yardstick is made of.

``run.py`` is the entry point. The modules here hold what a later PR may
not change: how cells are found (``spec``), the device gate and the table
of peaks (``device``), operation counts (``flops``), the traffic and data
generators (``traffic``, ``tokens``), the reduction from a profiler trace
to numbers (``trace``), the comparison with the plain reference
(``check``), and the two drivers (``train_cell``, ``serve_cell``).

Nothing here imports JAX while it is imported: the modules that need it
import it inside their functions, so ``run.py`` can refuse a machine
without a chip before any backend is touched, and the tests of the pure
parts run without one.
"""
