"""The FCP trainer's chip benchmark: everything it measures with.

Each configuration, traffic mix, driver, metric and limit set is a data
file or a small module of its own, found by the name that
``BENCHMARK.json`` gives it (:mod:`chipbench.registry`).  The modules of
this package are the yardstick: traffic generation, weights from a seed,
FLOP and byte counts, the table of peaks, the reduction of a profiler
trace, the plain float32 reference of the train step and the comparison
that decides ``correct``.  None of them imports the program under test
except :mod:`chipbench.harness` and the drivers, which drive it.
"""
