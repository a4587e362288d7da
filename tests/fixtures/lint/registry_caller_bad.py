"""Call sites with out-of-registry literals and one dynamic name.
``FAULTS`` / ``TRACE`` are local stubs — the analyzer matches the
call shape, the file is never imported."""


class _Stub:
    def check(self, site):
        pass

    def span(self, name, **kw):
        pass

    def event(self, name, **kw):
        pass

    def phase(self, name, metrics=None, timer=None, **kw):
        pass

    def lap(self, name, **kw):
        pass


FAULTS = _Stub()
TRACE = _Stub()


def _expo_family(name, kind, help_):
    return {}


_ROGUE = _expo_family("rogue_metric", "counter", "x")  # finding: not in METRIC_NAMES


def run(name):
    FAULTS.check("rogue.site")  # finding: not in SITES
    TRACE.span("rogue.span")  # finding: not in SPAN_NAMES
    TRACE.event("rogue.event")  # finding: not in EVENT_NAMES
    TRACE.event(name)  # finding: non-literal name
    _expo_family(name, "counter", "x")  # finding: non-literal family
    TRACE.phase("rogue.phase", None, "t")  # finding: not in SPAN_NAMES
    sp = TRACE.span("wired.site")
    sp.lap("rogue.lap")  # finding: not in SPAN_NAMES
    TRACE.stage("rogue.stage")  # finding: not in SPAN_NAMES
    sp.lap(name)  # finding: non-literal name
