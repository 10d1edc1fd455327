"""Plain references the benchmark judges the program against.

Nothing here imports the program under test (``src/repro``) or takes
anything it made: profiles, tables and machine parameters come from
the trace arrays and from the configuration files under
``bench/configs``.
"""
