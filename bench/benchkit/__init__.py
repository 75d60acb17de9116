"""The benchmark's own code: what a change that claims a gain may not edit.

Traffic generation lives in ``bench/traffic``, per-layer readers in
``bench/metrics``; this package holds the harness, the trace reduction,
the table of peaks, the work counts and the plain reference.
"""
