"""Experiment persistence: save/restore fuzz+minimization artifacts.

Reference: verification/Serialization.scala (526 LoC). The reference uses
Java serialization with heavy sanitization (closures → fingerprints,
ActorRefs re-resolved by re-booting a system, Serialization.scala:124-155).
Here everything is *structural JSON*: DSL messages are int tuples, external
events serialize as records, and deserialization rebuilds constructors from
the app definition — no code objects on disk, diffable experiment dirs.

Layout of an experiment dir (reference files in parens):
  metadata.json             (lifecycle.py capture)
  externals.json            (original_externals.bin)
  event_trace.json          (event_trace.bin)
  violation.json            (violation.bin)
  mcs.json                  (mcs.bin)                [optional]
  minimized_trace.json      (minimizedInternalTrace.bin) [optional]
  minimization_stats.json   (minimization_stats.json)   [optional]
"""

from __future__ import annotations

import json
import os
import platform
import subprocess
import time
from typing import Any, Dict, List, Optional, Sequence

from .dsl import DSLApp
from .events import (
    WildCardMatch,
    BeginExternalAtomicBlock,
    BeginUnignorableEvents,
    BeginWaitCondition,
    BeginWaitQuiescence,
    CodeBlockEvent,
    EndExternalAtomicBlock,
    EndUnignorableEvents,
    Event,
    HardKillEvent,
    KillEvent,
    MsgDiscarded,
    MsgEvent,
    MsgKept,
    MsgSend,
    PartitionEvent,
    Quiescence,
    SpawnEvent,
    TimerDelivery,
    UnPartitionEvent,
    Unique,
)
from .external_events import (
    ExternalEvent,
    HardKill,
    Kill,
    MessageConstructor,
    Partition,
    Send,
    Start,
    UnPartition,
    WaitCondition,
    WaitQuiescence,
    ensure_eid_floor,
)
from .minimization.stats import MinimizationStats
from .minimization.test_oracle import IntViolation
from .runtime.actor import dsl_actor_factory
from .trace import EventTrace

_EVENT_TYPES = {
    "msg_send": MsgSend,
    "msg_event": MsgEvent,
    "msg_kept": MsgKept,
    "msg_discarded": MsgDiscarded,
    "timer_delivery": TimerDelivery,
    "spawn": SpawnEvent,
    "kill": KillEvent,
    "hardkill": HardKillEvent,
    "partition": PartitionEvent,
    "unpartition": UnPartitionEvent,
    "quiescence": Quiescence,
    "begin_wait_quiescence": BeginWaitQuiescence,
    "begin_wait_condition": BeginWaitCondition,
    "begin_unignorable": BeginUnignorableEvents,
    "end_unignorable": EndUnignorableEvents,
    "code_block": CodeBlockEvent,
}


def _msg_to_json(msg: Any):
    if isinstance(msg, WildCardMatch):
        # Wildcarded expected deliveries occur in minimization-stage
        # checkpoints (policy enum only; closure selectors don't persist,
        # matching the reference's sanitization).
        return {"t": "wc", "tag": msg.class_tag, "policy": msg.policy}
    if isinstance(msg, tuple):
        return {"t": "tuple", "v": list(int(x) for x in msg)}
    if isinstance(msg, (int, str, float, bool)) or msg is None:
        return {"t": "lit", "v": msg}
    return {"t": "repr", "v": repr(msg)}


def _msg_from_json(obj):
    if obj["t"] == "wc":
        return WildCardMatch(class_tag=obj["tag"], policy=obj["policy"])
    if obj["t"] == "tuple":
        return tuple(obj["v"])
    return obj["v"]


def _fp_to_json(fp: Any):
    """Fingerprints are nested tuples/scalars; JSON lists don't round-trip
    to tuples, so encode structure explicitly."""
    if isinstance(fp, tuple):
        return {"t": "tuple", "v": [_fp_to_json(x) for x in fp]}
    return {"t": "lit", "v": fp}


def _fp_from_json(obj) -> Any:
    if obj["t"] == "tuple":
        return tuple(_fp_from_json(x) for x in obj["v"])
    return obj["v"]


def _event_to_json(u: Unique) -> Dict[str, Any]:
    e = u.event
    rec: Dict[str, Any] = {"id": u.id}
    if isinstance(e, MsgSend):
        rec.update(type="msg_send", snd=e.snd, rcv=e.rcv, msg=_msg_to_json(e.msg))
    elif isinstance(e, MsgEvent):
        rec.update(type="msg_event", snd=e.snd, rcv=e.rcv, msg=_msg_to_json(e.msg))
    elif isinstance(e, (MsgKept, MsgDiscarded)):
        rec.update(
            type="msg_kept" if isinstance(e, MsgKept) else "msg_discarded",
            snd=e.snd, rcv=e.rcv, msg=_msg_to_json(e.msg),
        )
    elif isinstance(e, TimerDelivery):
        rec.update(type="timer_delivery", rcv=e.rcv, msg=_msg_to_json(e.msg))
    elif isinstance(e, SpawnEvent):
        rec.update(type="spawn", name=e.name)
    elif isinstance(e, KillEvent):
        rec.update(type="kill", name=e.name)
    elif isinstance(e, HardKillEvent):
        rec.update(type="hardkill", name=e.name)
    elif isinstance(e, PartitionEvent):
        rec.update(type="partition", a=e.a, b=e.b)
    elif isinstance(e, UnPartitionEvent):
        rec.update(type="unpartition", a=e.a, b=e.b)
    elif isinstance(e, CodeBlockEvent):
        rec.update(type="code_block", label=e.label)
    elif isinstance(e, Quiescence):
        rec.update(type="quiescence")
    elif isinstance(e, BeginWaitQuiescence):
        rec.update(type="begin_wait_quiescence")
    elif isinstance(e, BeginWaitCondition):
        rec.update(type="begin_wait_condition")
    elif isinstance(e, BeginUnignorableEvents):
        rec.update(type="begin_unignorable")
    elif isinstance(e, EndUnignorableEvents):
        rec.update(type="end_unignorable")
    elif isinstance(e, BeginExternalAtomicBlock):
        rec.update(type="begin_atomic", block=e.block_id)
    elif isinstance(e, EndExternalAtomicBlock):
        rec.update(type="end_atomic", block=e.block_id)
    else:
        raise TypeError(f"unserializable event {e!r}")
    return rec


def _event_from_json(rec: Dict[str, Any], app: Optional[DSLApp]) -> Unique:
    t = rec["type"]
    if t == "msg_send":
        e: Event = MsgSend(rec["snd"], rec["rcv"], _msg_from_json(rec["msg"]))
    elif t in ("msg_event", "msg_kept", "msg_discarded"):
        e = _EVENT_TYPES[t](rec["snd"], rec["rcv"], _msg_from_json(rec["msg"]))
    elif t == "timer_delivery":
        e = TimerDelivery(rec["rcv"], _msg_from_json(rec["msg"]))
    elif t == "spawn":
        ctor = None
        if app is not None:
            ctor = dsl_actor_factory(app, app.actor_id(rec["name"]))
        e = SpawnEvent("__external__", rec["name"], ctor=ctor)
    elif t == "kill":
        e = KillEvent(rec["name"])
    elif t == "hardkill":
        e = HardKillEvent(rec["name"])
    elif t == "partition":
        e = PartitionEvent(rec["a"], rec["b"])
    elif t == "unpartition":
        e = UnPartitionEvent(rec["a"], rec["b"])
    elif t == "code_block":
        e = CodeBlockEvent(rec.get("label", ""))
    elif t == "begin_atomic":
        e = BeginExternalAtomicBlock(rec["block"])
    elif t == "end_atomic":
        e = EndExternalAtomicBlock(rec["block"])
    else:
        e = _EVENT_TYPES[t]()
    return Unique(e, rec["id"])


def _external_to_json(e: ExternalEvent) -> Dict[str, Any]:
    rec: Dict[str, Any] = {"eid": e.eid}
    if e.block_id is not None:
        rec["block"] = e.block_id
    if isinstance(e, Start):
        rec.update(type="start", name=e.name)
    elif isinstance(e, Kill):
        rec.update(type="kill", name=e.name)
    elif isinstance(e, HardKill):
        rec.update(type="hardkill", name=e.name)
    elif isinstance(e, Send):
        rec.update(type="send", name=e.name, msg=_msg_to_json(e.message()))
    elif isinstance(e, WaitQuiescence):
        rec.update(type="wait_quiescence", budget=e.budget)
    elif isinstance(e, WaitCondition) and e.cond_id is not None:
        # The cond_id form is closure-free (names a DSLApp.conditions
        # entry) and round-trips; the host-closure form below does not.
        rec.update(type="wait_condition", cond_id=e.cond_id, budget=e.budget)
    elif isinstance(e, Partition):
        rec.update(type="partition", a=e.a, b=e.b)
    elif isinstance(e, UnPartition):
        rec.update(type="unpartition", a=e.a, b=e.b)
    else:
        raise TypeError(
            f"{type(e).__name__} is not serializable (closure-form "
            "WaitCondition/CodeBlock close over host code; reference "
            "sanitization drops them too)"
        )
    return rec


def _external_from_json(rec: Dict[str, Any], app: Optional[DSLApp]) -> ExternalEvent:
    t = rec["type"]
    if t == "start":
        ctor = None
        if app is not None:
            ctor = dsl_actor_factory(app, app.actor_id(rec["name"]))
        e: ExternalEvent = Start(rec["name"], ctor=ctor)
    elif t == "kill":
        e = Kill(rec["name"])
    elif t == "hardkill":
        e = HardKill(rec["name"])
    elif t == "send":
        msg = _msg_from_json(rec["msg"])
        e = Send(rec["name"], MessageConstructor(lambda m=msg: m))
    elif t == "wait_quiescence":
        e = WaitQuiescence(budget=rec.get("budget"))
    elif t == "wait_condition":
        e = WaitCondition(cond_id=rec["cond_id"], budget=rec.get("budget"))
    elif t == "partition":
        e = Partition(rec["a"], rec["b"])
    elif t == "unpartition":
        e = UnPartition(rec["a"], rec["b"])
    else:
        raise TypeError(f"unknown external record {t!r}")
    # Restore the recorded identity: minimization artifacts reference
    # events by eid (reference: ids preserved via the saved IDGenerator
    # state, Serialization.scala:181-182,318-321). Advance the global
    # counter so fresh events never alias restored ones.
    object.__setattr__(e, "eid", rec["eid"])
    ensure_eid_floor(rec["eid"])
    if rec.get("block") is not None:
        # Block ids ride the eid counter; floor past them too so fresh
        # blocks never alias restored ones.
        object.__setattr__(e, "block_id", rec["block"])
        ensure_eid_floor(rec["block"])
    return e


def _metadata() -> Dict[str, Any]:
    """Reference: src/main/python/lifecycle.py — host/git capture."""
    meta = {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "host": platform.node(),
        "platform": platform.platform(),
    }
    try:
        meta["git_sha"] = (
            subprocess.run(
                ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
                timeout=5, cwd=os.path.dirname(os.path.abspath(__file__)),
            ).stdout.strip()
        )
    except Exception:
        pass
    return meta


class ExperimentSerializer:
    @staticmethod
    def save(
        directory: str,
        externals: Sequence[ExternalEvent],
        trace: EventTrace,
        violation: Any,
        app_name: str = "",
        mcs: Optional[Sequence[ExternalEvent]] = None,
        minimized_trace: Optional[EventTrace] = None,
        stats: Optional[MinimizationStats] = None,
        device_trace=None,  # int32 [rows, rec_width] device records
    ) -> str:
        os.makedirs(directory, exist_ok=True)

        def write(name: str, obj) -> None:
            with open(os.path.join(directory, name), "w") as f:
                json.dump(obj, f, indent=1)

        write("metadata.json", {**_metadata(), "app": app_name})
        write("externals.json", [_external_to_json(e) for e in externals])
        write("event_trace.json", [_event_to_json(u) for u in trace.events])
        if isinstance(violation, IntViolation):
            write(
                "violation.json",
                {"code": violation.code, "nodes": list(violation.nodes)},
            )
        if mcs is not None:
            write("mcs.json", [e.eid for e in mcs])
        if minimized_trace is not None:
            write(
                "minimized_trace.json",
                [_event_to_json(u) for u in minimized_trace.events],
            )
        if stats is not None:
            with open(os.path.join(directory, "minimization_stats.json"), "w") as f:
                f.write(stats.to_json())
        if device_trace is not None:
            from .native import write_record_log

            write_record_log(
                os.path.join(directory, "device_trace.demirec"), device_trace
            )
        return directory


def save_dep_graph(directory: str, tracker) -> str:
    """Persist a DepTracker's happens-before forest (reference: depGraph
    nodes/edges, Serialization.scala:176-187, 391-421) so restartable
    minimization can re-seed DPOR without re-running the recording."""
    os.makedirs(directory, exist_ok=True)
    records = []
    for rec in tracker.to_records():
        rec = dict(rec)
        rec["fp"] = _fp_to_json(rec["fp"])
        records.append(rec)
    path = os.path.join(directory, "dep_graph.json")
    with open(path, "w") as f:
        json.dump(records, f, indent=1)
    return path


def _warn_corrupt(path: str, exc: Exception) -> None:
    """A truncated or unparsable checkpoint behaves like an ABSENT one
    (the --resume run redoes that stage) instead of crashing — but never
    silently: warn + ``persist.stage_corrupt`` (force-written so the
    degradation reaches every snapshot regardless of DEMI_OBS)."""
    import sys

    from . import obs

    obs.counter("persist.stage_corrupt").force_inc()
    print(
        f"demi_tpu: checkpoint {path!r} is corrupt or truncated "
        f"({type(exc).__name__}: {exc}); treating it as absent",
        file=sys.stderr,
    )


def load_dep_graph(directory: str, fingerprinter):
    """Rebuild the DepTracker saved by save_dep_graph; None if absent —
    or corrupt/truncated (warn + counter, treat as absent: a damaged
    artifact must degrade a --resume run, never crash it)."""
    from .schedulers.dep_tracker import DepTracker

    path = os.path.join(directory, "dep_graph.json")
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            records = json.load(f)
        for rec in records:
            rec["fp"] = _fp_from_json(rec["fp"])
        return DepTracker.from_records(records, fingerprinter)
    except Exception as exc:
        _warn_corrupt(path, exc)
        return None


def save_stage(
    directory: str,
    stage: str,
    externals: Sequence[ExternalEvent],
    trace: EventTrace,
) -> None:
    """Checkpoint one minimization-pipeline stage's outputs (reference:
    every gamut stage's trace is serialized for restart,
    RunnerUtils.scala:171-500 + deserializeExperiment:502-525)."""
    os.makedirs(directory, exist_ok=True)
    obj = {
        "stage": stage,
        "externals": [_external_to_json(e) for e in externals],
        "trace": [_event_to_json(u) for u in trace.events],
    }
    with open(os.path.join(directory, f"stage_{stage}.json"), "w") as f:
        json.dump(obj, f, indent=1)


def load_stage(directory: str, stage: str, app: Optional[DSLApp] = None):
    """(externals, trace) for a checkpointed stage, or None if absent —
    or truncated/unparsable (warn + counter, treat as absent so a
    --resume run redoes the stage instead of crashing)."""
    path = os.path.join(directory, f"stage_{stage}.json")
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            obj = json.load(f)
        externals = [_external_from_json(r, app) for r in obj["externals"]]
        events = [_event_from_json(r, app) for r in obj["trace"]]
        return externals, EventTrace(events, externals)
    except Exception as exc:
        _warn_corrupt(path, exc)
        return None


class ExperimentDeserializer:
    def __init__(self, directory: str, app: Optional[DSLApp] = None):
        self.directory = directory
        self.app = app

    def _read(self, name: str, required: bool = False):
        path = os.path.join(self.directory, name)
        if not os.path.exists(path):
            if required:
                raise FileNotFoundError(
                    f"not an experiment dir: {self.directory!r} has no {name}"
                )
            return None
        with open(path) as f:
            return json.load(f)

    def get_externals(self) -> List[ExternalEvent]:
        return [
            _external_from_json(r, self.app)
            for r in self._read("externals.json", required=True)
        ]

    def get_trace(self, externals: Optional[Sequence[ExternalEvent]] = None) -> EventTrace:
        events = [
            _event_from_json(r, self.app)
            for r in self._read("event_trace.json", required=True)
        ]
        return EventTrace(events, list(externals) if externals else None)

    def get_violation(self) -> Optional[IntViolation]:
        rec = self._read("violation.json")
        if rec is None:
            return None
        return IntViolation(rec["code"], tuple(rec["nodes"]))

    def get_mcs(self, externals: Sequence[ExternalEvent]) -> Optional[List[ExternalEvent]]:
        eids = self._read("mcs.json")
        if eids is None:
            return None
        by_eid = {e.eid: e for e in externals}
        return [by_eid[i] for i in eids]

    def get_stats(self) -> Optional[MinimizationStats]:
        path = os.path.join(self.directory, "minimization_stats.json")
        if not os.path.exists(path):
            return None
        with open(path) as f:
            return MinimizationStats.from_json(f.read())

    def get_device_trace(self):
        path = os.path.join(self.directory, "device_trace.demirec")
        if not os.path.exists(path):
            return None
        from .native import read_record_log

        return read_record_log(path)
