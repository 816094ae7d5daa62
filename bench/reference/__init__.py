"""Plain references, one module per program family.

Written from the PolyBench/C 4.2.1 sources in straightforward
``jax.numpy``.  They import nothing of ``repro``: ``correct`` compares
the compiled program with these.  Each module has ``reference(cfg,
env)`` at the precision the configuration states and ``control(cfg,
env)``, the same computation one precision lower, which the comparison
has to fail.
"""
