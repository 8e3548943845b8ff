"""The benchmark: one command runs one cell (configuration x traffic mix)
once and prints one result line. See PERF.md for the contract it keeps and
how a later PR adds a cell, a configuration or a per-layer metric as files.
"""
