"""The benchmark's own code: cell loading, traffic, clients, weights, the plain
reference, trace reduction, peaks and operation counts. It imports nothing of
the program under ``src/`` except in ``runner.py`` and ``model.py``, which drive
the system under test."""
