"""Every literal resolves into its registry: zero findings expected
(together with registry_replay_clean.py as the replay module)."""


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


def run():
    with_span = TRACE.span("wired.site")
    with_span.lap("wired.site")
    TRACE.phase("wired.site", None, "wired")
    FAULTS.check("wired.site")
    TRACE.event("fault.fired", site="wired.site")
    return with_span
