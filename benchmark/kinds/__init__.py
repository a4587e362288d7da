"""Generator kinds: ``<kind>.py`` gives ``inputs(config, traffic, seed) ->
dict`` with ``body`` (the request's bytes) and ``units``, and for jobs
``operations`` and ``steps`` (``generators.job_inputs``).  A configuration
chooses one by ``generator.kind`` (``run.build_inputs``)."""
