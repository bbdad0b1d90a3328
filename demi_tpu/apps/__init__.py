from ..obs import spans as _spans

with _spans.stage("setup.import", module=__name__):
    from .common import make_host_invariant, dsl_start_events, DSLSendGenerator

__all__ = ["make_host_invariant", "dsl_start_events", "DSLSendGenerator"]
