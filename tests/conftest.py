"""Test env: CPU backend with 8 virtual devices (multi-chip sharding tests
run on a virtual mesh — real multi-chip hardware is validated separately by
the driver via __graft_entry__.dryrun_multichip), x64 enabled so the exact
int64 parity paths are active.

The platform is pinned through jax.config.update BEFORE any device call,
so the suite never initialises (or holds) an accelerator whatever
JAX_PLATFORMS says.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
# XLA:CPU runs all 8 virtual devices' thunks on ONE pool sized to the core
# count, and an in-process all-reduce blocks its pool thread until every
# device joins.  On an 8-core host a device that starts two independent
# all-reduces takes two threads, the eighth device never gets one, and
# rendezvous.cc aborts the whole pytest process after 40 s ("Termination
# timeout ... only 7 of them arrived"; pytest's fd capture swallows the
# message — rerun with -s).  Seen in 5 of 22 runs of
# test_device_sharded_explicit_mesh_contract, seed tree included; 0 of 24
# with the pool at 32 threads.
os.environ.setdefault("PJRT_NPROC", "32")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

assert jax.device_count() >= 8, "virtual device mesh not active"

# Persistent compile cache (ksim_tpu.util.enable_compilation_cache:
# JAX_COMPILATION_CACHE_DIR if set, else <repo>/.jax_cache/<host>): the
# suite compiles many hundreds of XLA:CPU programs in one process, and
# this image's jaxlib segfaulted inside LLVM codegen late in two
# full-suite runs (reproducibly ~92% in, never in isolation).  A warm
# cache drops the per-process compile count to ~zero, which both
# sidesteps the crash and cuts suite wall-clock.
import sys as _sys  # noqa: E402

_sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from ksim_tpu.util import enable_compilation_cache  # noqa: E402

enable_compilation_cache()

# The real round-4 crash root cause: a full-suite process accumulates
# ~8k memory maps/min (every XLA:CPU executable mmaps code pages) and
# dies at the kernel's vm.max_map_count (65530 default) — SIGSEGV when
# it hits during LLVM codegen, SIGABRT during cache deserialization,
# always ~92% through the suite, never in half-suite runs (observed
# maps=62797 ten seconds before death).  Two best-effort guards: raise
# the limit (this image runs as root), and shed live executables when
# the map count nears the ceiling.


from ksim_tpu.util import raise_map_count_limit  # noqa: E402

raise_map_count_limit()

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _shed_executables_when_map_bound_nears():
    yield
    try:
        with open("/proc/self/maps") as f:
            n = sum(1 for _ in f)
    except OSError:
        return
    if n > 40_000:
        jax.clear_caches()
