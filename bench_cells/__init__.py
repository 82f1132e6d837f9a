"""The benchmark: cells, metrics and references named by ``BENCHMARK.json``.

Nothing here is imported by the program, and the yardstick parts (traffic,
trace reduction, peaks, operation counts, references, the comparison that
decides ``correct``) import nothing of the program. Only ``runners/`` calls
into ``simple_distributed_machine_learning_tpu``: they hold the system under
test.
"""
