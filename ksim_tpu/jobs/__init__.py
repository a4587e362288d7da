"""Tenant job plane: queue + worker pool + per-job isolation planes.

See ksim_tpu/jobs/manager.py for the subsystem docstring, docs/jobs.md
for the API, queue semantics and tenancy model, and
ksim_tpu/jobs/fleet.py for the multi-worker fleet (lease-claimed jobs
over one shared journal)."""

from ksim_tpu.jobs.fleet import FileLock, FleetMember, JournalTailer, LeasePlane
from ksim_tpu.jobs.journal import JobJournal
from ksim_tpu.jobs.manager import (
    JOB_FAULT_SITES,
    TERMINAL_STATES,
    Job,
    JobClock,
    JobLimitExceeded,
    JobManager,
    JobThrottled,
    parse_job_faults,
)
from ksim_tpu.jobs.queue import JobQueue, JobQueueFull

__all__ = [
    "JOB_FAULT_SITES",
    "TERMINAL_STATES",
    "FileLock",
    "FleetMember",
    "Job",
    "JobClock",
    "JobJournal",
    "JobLimitExceeded",
    "JobManager",
    "JobQueue",
    "JobQueueFull",
    "JobThrottled",
    "JournalTailer",
    "LeasePlane",
    "parse_job_faults",
]
