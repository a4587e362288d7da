# Namespace for developer tooling (tools.ksimlint et al.).  The scripts
# in this directory (trace_check.py, shard_repro.py) are still run as
# plain scripts; the package __init__ only exists so the analyzer is
# importable as ``tools.ksimlint`` from the repo root.
