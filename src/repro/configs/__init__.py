"""The paper's own experiment configuration (``paper.py``): the Table-1
dataset registry keys and the per-figure benchmark settings that
``benchmarks/run.py`` sweeps."""
