"""Per-layer metrics, one module each, found by the metric's name in
``BENCHMARK.json``. A module gives ``LAYER``, ``UNIT`` and ``MOVES`` (the
end-to-end metric it should move) and ``read(run)``, which returns the
number or ``None`` where the run has nothing to read; ``run`` is the
harness's :class:`bench.run.Window`."""
