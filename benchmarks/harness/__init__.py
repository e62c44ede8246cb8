"""The yardstick: window loop, arithmetic, evidence checks, trace
reduction and peaks. Holds no cell, configuration or metric name — those
are files of their own, found by the names in ``BENCHMARK.json``
(``discover.py``)."""
