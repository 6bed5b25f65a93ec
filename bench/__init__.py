"""On-chip benchmark of the summarization service: one cell per run
(``python bench/run.py --workload <cell> ...``), driven by BENCHMARK.json."""
