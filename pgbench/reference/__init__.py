"""Plain references for the benchmark's output checks.

Written from the published semantics (ProbGraph's Bloom sketch and
estimators, Graph500 graphs, the serving queries), in numpy, importing
nothing of the program under test and taking nothing it made.
"""
