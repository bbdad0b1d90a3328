"""The bundled apps, written once against the DSL and run on both tiers:
``broadcast``, ``chain`` (chain replication with its failure repairs, over
FIFO channels), ``kafka`` (partition replication as KIP-101 found it:
many groups over one set of brokers, a controller, FIFO links), ``paxos``
(Multi-Paxos as published, over datagram channels), ``raft``,
``raft_reconfig`` (the dissertation's Raft: persistent state,
single-server membership changes, InstallSnapshot), ``spark_dag``,
``twopc``, ``vsr`` (Viewstamped Replication Revisited). ``cli.build_app``
names them for ``--app``."""

from ..obs import spans as _spans

with _spans.stage("setup.import", module=__name__):
    from .common import make_host_invariant, dsl_start_events, DSLSendGenerator

__all__ = ["make_host_invariant", "dsl_start_events", "DSLSendGenerator"]
