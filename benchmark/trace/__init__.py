"""From the profiler's trace to numbers: ``xplane.py`` reads an
``.xplane.pb`` with ``jax.profiler.ProfileData`` into plain Python,
``hlo.py`` reads the compiled step's HLO text, ``reduce.py`` holds the
arithmetic (busy union, idle share, time by scope, exposed collectives, the
breakdown).  ``python3 -m benchmark.trace.dump <file>`` shows what a trace
holds."""
