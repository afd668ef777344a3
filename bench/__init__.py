"""On-chip benchmark of the served retrieval path (see ``BENCHMARK.json``).

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`` runs
one cell once. Everything that measures lives here: the corpus and traffic
generators, the index store cache, the plain exact reference and the comparison that
decides ``correct``, the reduction from a profiler trace to metrics, the kernels'
operation and byte counts and the table of device peaks. From the program the
benchmark takes only the system under test (``repro.api.Retriever``) and what it
reports about each answer.
"""
