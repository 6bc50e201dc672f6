"""The benchmark's own code: traffic, weights, checks, trace reduction.

Nothing here is imported by the program; from the program the benchmark
takes only the system under test (``Trainer``, ``ServingEngine`` behind
``InferenceServer``) and what those objects record about themselves.
"""
