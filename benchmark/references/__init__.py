"""Plain references of the job deployments, by name: ``<name>.py`` gives
``replay(operations, *, precision, max_pods_per_pass) -> dict`` as
``replay.py`` does (the three counts and ``placements``) and raises
``replay.NotCovered`` for what it does not evaluate.  A configuration names
one with ``"reference": "<name>"`` (``run.reference_of``); without the key it
is ``replay.py``, the sequential scheduler with the default plugins and
weights.  This is where a deployment that ``replay.py`` will never hold
(sampled scoring, a bin-packing profile, volumes) brings its own copy of the
reference, as a new file."""
