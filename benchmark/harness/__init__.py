"""The benchmark's own code: everything that decides a number or `correct`.

Nothing here is imported by the program, and the only things taken from the
program are the system under test (deployed through ``SiddhiManager``) and
its counters (``bridge.probe``, ``bridge.driver``, ``bridge.guard``).
"""
