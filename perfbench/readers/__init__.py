"""One reader per kind of reading; a per-layer metric's data file names its
reader.  ``read(spec, run)`` returns the number, or None where it finds
nothing to read (the harness then leaves the metric out of the line).

``run`` holds what a traced run gathered: ``before``/``after`` (parsed
/metrics of each tier at the window's edges), ``spans`` (span lists of a
sample of requests), ``trace`` (reduce_trace's dictionary), ``outcomes``
(the window's requests), ``seconds``, ``config``, ``traffic``, ``peaks``.
"""
