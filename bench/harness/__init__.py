"""The benchmark's own code: cell loading, traffic, clients, weights, trace
reduction, peaks and operation counts; each architecture's layout, plain
reference and work per token are a model module in ``bench/models/``. It
imports nothing of the program under ``src/`` except in ``runner.py`` and
``model.py``, which drive the system under test."""
