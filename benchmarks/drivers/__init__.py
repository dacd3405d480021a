"""One module per kind of deployment.  A driver exposes
``Driver(config, traffic, seed, scratch_dir)`` with ``setup()``, ``warm()``,
``window(seconds, tracer)``, ``check()`` and ``close()``; run.py finds it by
the configuration's ``driver`` key."""
