"""Sentinel errors (analogue of reference simulator/errors/errors.go)."""


class SimulatorError(Exception):
    """Base class for simulator errors."""


class NotFoundError(SimulatorError):
    """Requested resource does not exist (reference: errors.ErrNotFound)."""


class ConflictError(SimulatorError):
    """Optimistic-concurrency conflict on a resource update."""


class InvalidConfigError(SimulatorError):
    """Configuration failed validation."""


class ExpiredError(SimulatorError):
    """A watch resume point fell out of the event history — the "410
    Gone" etcd compaction analogue; the client must relist."""


class DeviceUnavailableError(SimulatorError):
    """The accelerator backend failed or stopped answering — an XLA
    runtime error, a hung backend, or a dispatch that outlived its
    watchdog.  Consumers must DEGRADE (host path, circuit breaker)
    rather than crash: the condition is environmental, not a bug."""


class RunCancelled(Exception):
    """A scenario run was cancelled cooperatively (the job plane's
    DELETE /api/v1/jobs/<id>).  Deliberately NOT a SimulatorError: the
    replay's classified fault handlers absorb SimulatorErrors into
    per-pass fallbacks, and a cancellation must propagate out of the
    run — after the in-flight segment transaction rolled back — rather
    than be retried on the host path."""


class ReplayFallback(SimulatorError):
    """A replay segment cannot (or must not) run on-device and should
    take the per-pass host path instead.  ``reason`` is the stable
    string the fallback histogram buckets on (engine/replay.py
    ``ReplayDriver.unsupported``)."""

    def __init__(self, reason: str = "replay_fallback") -> None:
        super().__init__(reason)
        self.reason = reason
