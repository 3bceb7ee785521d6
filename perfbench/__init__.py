"""On-chip benchmark of FedDec training rounds (see BENCHMARK.json).

Everything that measures lives here: traffic generation, the weights made
from the seed, the plain float32 reference of Algorithm 1 that decides
``correct``, the model-FLOP and kernel-byte counts, the table of chip peaks
and the reduction of profiler traces to per-layer metrics.  From the
program under test (``src/repro``) the benchmark takes only the round
executor it times, with its compiled programs and kernel names.
"""
