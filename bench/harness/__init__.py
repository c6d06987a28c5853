"""The benchmark's own machinery: data, the training runner, checks.

Nothing under ``src/`` imports this package, and this package takes from
the program only the system under test (`repro.core.boosting.SketchBoost`)
and the names of its kernels.
"""
