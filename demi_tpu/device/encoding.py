"""Host↔device lowering: external-event programs, expected traces, and
device-trace reconstruction.

The host tier owns trace surgery (subsequence intersection, wildcarding);
this module lowers its outputs to the int32 record/op encodings the kernels
consume, and lifts device explore traces back into host EventTraces (by
guided re-execution on the host oracle, so the lifted trace carries proper
Unique ids, MsgSends, and markers for the minimization stack).
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import obs
from ..dsl import DSLApp
from ..events import (
    BeginWaitCondition,
    BeginWaitQuiescence,
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
)
from ..external_events import (
    ExternalEvent,
    HardKill,
    Kill,
    Partition,
    Send,
    Start,
    UnPartition,
    WaitCondition,
    WaitQuiescence,
)
from ..events import WildCardMatch
from ..fuzzing.program import ACTOR_KINDS, FuzzProgram
from ..trace import EventTrace
from .core import (
    OP_END,
    OP_HARDKILL,
    OP_KILL,
    OP_PARTITION,
    OP_SEND,
    OP_START,
    OP_UNPARTITION,
    OP_WAIT,
    OP_WAITCOND,
    HASH_DISCARDED,
    HASH_KEPT,
    REC_DELIVERY,
    REC_DISCARDED,
    REC_EXT_BASE,
    REC_KEPT,
    REC_NONE,
    REC_TIMER,
    REC_WILDCARD,
    DeviceConfig,
)
from .explore import ExtProgram


def _msg_row(app: DSLApp, msg, width: int) -> List[int]:
    row = list(int(x) for x in msg)
    assert len(row) <= width, f"message {msg!r} wider than msg_width={width}"
    return row + [0] * (width - len(row))


def empty_programs(cfg: DeviceConfig, lanes: int) -> ExtProgram:
    """``lanes`` all-``OP_END`` programs as one set of host arrays, for
    ``lower_into`` to write lane by lane."""
    e, w = cfg.max_external_ops, cfg.msg_width
    return ExtProgram(
        op=np.zeros((lanes, e), np.int32),
        a=np.zeros((lanes, e), np.int32),
        b=np.zeros((lanes, e), np.int32),
        msg=np.zeros((lanes, e, w), np.int32),
    )


def lower_program(
    app: DSLApp, cfg: DeviceConfig, externals: Sequence[ExternalEvent]
) -> ExtProgram:
    """Lower an external-event program to op arrays. WaitCondition lowers
    via its ``cond_id`` (DSLApp.conditions); host-closure WaitCondition
    and CodeBlock are host-tier-only and rejected here."""
    out = empty_programs(cfg, 1)
    lower_into(app, cfg, externals, out, 0)
    return ExtProgram(*(x[0] for x in out))


def lower_into(
    app: DSLApp,
    cfg: DeviceConfig,
    externals: Sequence[ExternalEvent],
    out: ExtProgram,
    lane: int,
) -> bool:
    """``lower_program`` into row ``lane`` of the batch arrays ``out``
    (``empty_programs``), over whatever program the lane held. A
    ``FuzzProgram`` (never edited: it has no way to be) is lowered from
    its op rows, and no event object is made; anything else, a
    minimizer's list or a hand-written program, through the per-event
    loop. Returns whether the rows were what was lowered."""
    # (``type is``: an ABC's isinstance costs a lane's share of the call.)
    from_rows = type(externals) is FuzzProgram and externals.lowerable
    n = len(externals.kind) if from_rows else len(externals)
    e = cfg.max_external_ops
    if n > e:
        raise ValueError(f"program length {n} > max_external_ops {e}")
    msg = out.msg[lane]
    msg.fill(0)
    if from_rows:
        _lower_rows(app, externals, [0] * (e - n), out, lane, msg)
    else:
        ops, a, b = out.op[lane], out.a[lane], out.b[lane]
        ops.fill(0)
        a.fill(0)
        b.fill(0)
        _lower_events(app, cfg.msg_width, externals, ops, a, b, msg)
    _check_msg_range(cfg, msg)
    return from_rows


def _lower_rows(
    app: DSLApp, prog: FuzzProgram, pad: List[int], out: ExtProgram,
    lane: int, msg: np.ndarray,
) -> None:
    """A fuzzed program's rows into lane ``lane`` of ``out`` (``msg``
    its zeroed payload block, ``pad`` the zeros up to the arrays'
    length): the columns as they are (the rows are in the device's
    numbering), one assignment each; the actor indices through the
    fuzzer's name table where that is not the app's own order; the
    payloads as the ints they are."""
    kind, col_a, col_b = prog.kind, prog.a, prog.b
    ids = prog.frame.actor_ids(app)
    if ids is not None:
        col_a = [ids[x] if k in ACTOR_KINDS else x for k, x in zip(kind, col_a)]
        col_b = [
            ids[x] if k == OP_PARTITION or k == OP_UNPARTITION else x
            for k, x in zip(kind, col_b)
        ]
    if OP_WAITCOND in kind:
        worst = max(x for k, x in zip(kind, col_a) if k == OP_WAITCOND)
        if worst >= len(app.conditions):
            raise ValueError(
                f"cond_id {worst} out of range for "
                f"{len(app.conditions)} app conditions"
            )
    out.op[lane] = kind + pad
    out.a[lane] = col_a + pad
    out.b[lane] = col_b + pad
    for i, payload in prog.payloads:
        msg[i, : len(payload)] = payload


def _lower_events(app: DSLApp, w: int, externals, ops, a, b, msg) -> None:
    """The per-event lowering, into one lane's zeroed arrays."""
    for i, ev in enumerate(externals):
        if isinstance(ev, Start):
            ops[i], a[i] = OP_START, app.actor_id(ev.name)
        elif isinstance(ev, Kill):
            ops[i], a[i] = OP_KILL, app.actor_id(ev.name)
        elif isinstance(ev, HardKill):
            ops[i], a[i] = OP_HARDKILL, app.actor_id(ev.name)
        elif isinstance(ev, Send):
            ops[i], a[i] = OP_SEND, app.actor_id(ev.name)
            msg[i] = _msg_row(app, ev.message(), w)
        elif isinstance(ev, WaitQuiescence):
            ops[i] = OP_WAIT
            a[i] = ev.budget or 0  # field a carries the bounded-wait budget
        elif isinstance(ev, WaitCondition):
            if ev.cond_id is None:
                raise TypeError(
                    "WaitCondition with a host closure is host-tier-only; "
                    "give the app a DSLApp.conditions table and pass "
                    "cond_id to lower it to the device tier"
                )
            if not (0 <= ev.cond_id < len(app.conditions)):
                raise ValueError(
                    f"cond_id {ev.cond_id} out of range for "
                    f"{len(app.conditions)} app conditions"
                )
            ops[i] = OP_WAITCOND
            a[i] = ev.cond_id
            b[i] = ev.budget or 0
        elif isinstance(ev, Partition):
            ops[i], a[i], b[i] = OP_PARTITION, app.actor_id(ev.a), app.actor_id(ev.b)
        elif isinstance(ev, UnPartition):
            ops[i], a[i], b[i] = OP_UNPARTITION, app.actor_id(ev.a), app.actor_id(ev.b)
        else:
            raise TypeError(f"{type(ev).__name__} is not lowerable to the device tier")


def _check_msg_range(cfg: DeviceConfig, msg: np.ndarray) -> None:
    """Narrow storage (msg_dtype='int16') silently wraps out-of-range
    payloads on device; reject them at the host lowering boundary."""
    if cfg.msg_dtype == "int16" and msg.size:
        lo, hi = np.iinfo(np.int16).min, np.iinfo(np.int16).max
        if msg.min() < lo or msg.max() > hi:
            raise ValueError(
                "message payload exceeds int16 range; use msg_dtype='int32' "
                f"(got values in [{msg.min()}, {msg.max()}])"
            )


def stack_programs(programs: Sequence[ExtProgram]) -> ExtProgram:
    return ExtProgram(
        op=np.stack([p.op for p in programs]),
        a=np.stack([p.a for p in programs]),
        b=np.stack([p.b for p in programs]),
        msg=np.stack([p.msg for p in programs]),
    )


#: The kinds by which a sweep counts the external ops it lowered
#: (``sweep.ops.<kind>``); ``restart`` is told from ``start`` below.
_OP_KIND = {
    OP_START: "start", OP_KILL: "kill", OP_SEND: "send", OP_WAIT: "wait",
    OP_WAITCOND: "wait", OP_PARTITION: "partition",
    OP_UNPARTITION: "unpartition", OP_HARDKILL: "hard_kill",
}


def count_ops(programs: Sequence[ExtProgram]) -> Dict[str, int]:
    """External ops of lowered programs, by kind (``count_op_arrays``)."""
    return count_op_arrays(
        np.stack([p.op for p in programs]), np.stack([p.a for p in programs])
    )


def count_op_arrays(op: np.ndarray, a: np.ndarray) -> Dict[str, int]:
    """External ops of the lowered programs ``op/a [n, E]``, by kind. A
    Start of an actor its program has started before (recovery) counts
    as ``restart``."""
    by_code = np.bincount(op.ravel(), minlength=max(_OP_KIND) + 1)
    counts: Dict[str, int] = {}
    for code, kind in _OP_KIND.items():
        counts[kind] = counts.get(kind, 0) + int(by_code[code])
    starts = op == OP_START
    first = np.zeros((len(op), int(a[starts].max(initial=0)) + 1), bool)
    first[np.nonzero(starts)[0], a[starts]] = True
    counts["restart"] = counts["start"] - int(first.sum())
    counts["start"] = int(first.sum())
    return counts


def _actor_or_external(app: DSLApp, name: str) -> int:
    try:
        return app.actor_id(name)
    except (KeyError, ValueError):
        return app.num_actors


def lower_expected_matrix(
    app: DSLApp,
    cfg: DeviceConfig,
    trace: EventTrace,
    externals: Sequence[ExternalEvent],
) -> Tuple[List[int], np.ndarray, np.ndarray]:
    """Matrix form of the expected-trace lowering: ``(uids, rows, mask)``
    where ``mask[k]`` marks trace event k as having a device row and
    ``rows`` is the [mask.sum(), 3 + msg_width] int32 matrix of those
    rows in trace order. The per-event dispatch writes straight into the
    preallocated matrix — no per-row Python list building — and every
    downstream consumer (``lower_expected_trace``, the
    ``CandidateLowerer`` full path, ``steering_prescription``) packs or
    filters it with array ops. A ``mask[k]=False`` event has no device
    meaning in replay (internal sends, wait/quiescence markers).

    Each row is a pure function of the event itself (plus its own
    external Send's re-bound payload), which is what makes the
    ``CandidateLowerer``'s row-gather sound: a candidate that is an
    event-subsequence of a base trace lowers to exactly the base's rows
    for the surviving uids."""
    w = cfg.msg_width
    rebound = trace.recompute_external_msg_sends(externals)
    n_events = len(trace.events)
    rows = np.zeros((n_events, 3 + w), np.int32)
    mask = np.zeros(n_events, bool)
    uids: List[int] = []
    uid_payload = {}
    k = 0
    for u, ev in zip(trace.events, rebound):
        uids.append(u.id)
        out = rows[k]
        if isinstance(ev, SpawnEvent):
            out[0], out[1] = REC_EXT_BASE + OP_START, app.actor_id(ev.name)
        elif isinstance(ev, KillEvent):
            out[0], out[1] = REC_EXT_BASE + OP_KILL, app.actor_id(ev.name)
        elif isinstance(ev, HardKillEvent):
            out[0], out[1] = REC_EXT_BASE + OP_HARDKILL, app.actor_id(ev.name)
        elif isinstance(ev, PartitionEvent):
            out[0] = REC_EXT_BASE + OP_PARTITION
            out[1], out[2] = app.actor_id(ev.a), app.actor_id(ev.b)
        elif isinstance(ev, UnPartitionEvent):
            out[0] = REC_EXT_BASE + OP_UNPARTITION
            out[1], out[2] = app.actor_id(ev.a), app.actor_id(ev.b)
        elif isinstance(ev, MsgSend):
            if ev.is_external:
                payload = _msg_row(app, ev.msg, w)
                uid_payload[u.id] = payload
                out[0], out[1] = REC_EXT_BASE + OP_SEND, app.actor_id(ev.rcv)
                out[3:] = payload
            else:
                continue  # internal sends re-occur as delivery side effects
        elif isinstance(ev, MsgEvent):
            if isinstance(ev.msg, WildCardMatch):
                wc = ev.msg
                if not isinstance(wc.class_tag, int):
                    raise TypeError(
                        "device wildcard replay needs int class tags "
                        f"(got {wc.class_tag!r})"
                    )
                if wc.selector is not None or wc.policy not in ("first", "last"):
                    raise TypeError(
                        f"wildcard policy {wc.policy!r}/selector is not "
                        "lowerable to the device tier"
                    )
                out[0], out[1] = REC_WILDCARD, app.actor_id(ev.rcv)
                out[2] = 1 if wc.policy == "last" else 0
                out[3] = wc.class_tag
            else:
                payload = uid_payload.get(u.id, None)
                if payload is None:
                    payload = _msg_row(app, ev.msg, w)
                out[0] = REC_DELIVERY
                out[1] = _actor_or_external(app, ev.snd)
                out[2] = app.actor_id(ev.rcv)
                out[3:] = payload
        elif isinstance(ev, TimerDelivery):
            rid = app.actor_id(ev.rcv)
            out[0], out[1], out[2] = REC_TIMER, rid, rid
            out[3:] = _msg_row(app, ev.msg, w)
        else:
            continue  # Quiescence / wait markers: no device meaning
        mask[len(uids) - 1] = True
        k += 1
    return uids, rows[:k], mask




def _pack_matrix(
    cfg: DeviceConfig, rows: np.ndarray, max_records: int
) -> np.ndarray:
    """Pad a compact [n, <=rec_width] int32 row matrix into the
    [max_records, rec_width] array the replay kernels consume, with the
    shared guards — the vectorized core of ``_pack_records``."""
    n = len(rows)
    if n > max_records:
        raise ValueError(f"expected trace has {n} records > {max_records}")
    # Records are compact (no mid-sequence REC_NONE holes): the replay
    # kernel's early-exit path terminates at the first zero-kind record,
    # which must therefore only ever be trailing padding. (ValueError, not
    # assert: this guard must survive python -O.)
    if n and (np.asarray(rows)[:, 0] == 0).any():
        raise ValueError("REC_NONE hole in expected trace records")
    # Rows are kind/a/b/msg; right-pad to the cfg's record width (a
    # record_parents cfg has a trailing parent column, zero here).
    out = np.zeros((max_records, cfg.rec_width), np.int32)
    if n:
        out[:n, : rows.shape[1]] = rows
    _check_msg_range(cfg, out[:, 3 : 3 + cfg.msg_width])
    return out


def _pack_records(
    cfg: DeviceConfig, recs: Sequence[Sequence[int]], max_records: int
) -> np.ndarray:
    """Assemble compact record rows into the padded [max_records,
    rec_width] array the replay kernels consume, with the shared guards.
    Uniform-width rows (the lowering always emits 3 + msg_width) stack in
    one array conversion; ragged inputs fall back to a per-row copy."""
    if len(recs) > max_records:
        raise ValueError(f"expected trace has {len(recs)} records > {max_records}")
    if not len(recs):
        return _pack_matrix(cfg, np.zeros((0, 3), np.int32), max_records)
    try:
        rows = np.asarray(recs, np.int32)
        assert rows.ndim == 2
    except (ValueError, AssertionError):
        if any(r[0] == 0 for r in recs):
            raise ValueError("REC_NONE hole in expected trace records")
        out = np.zeros((max_records, cfg.rec_width), np.int32)
        for i, r in enumerate(recs):
            out[i, : len(r)] = r
        _check_msg_range(cfg, out[:, 3 : 3 + cfg.msg_width])
        return out
    return _pack_matrix(cfg, rows, max_records)


def lower_expected_trace(
    app: DSLApp,
    cfg: DeviceConfig,
    trace: EventTrace,
    externals: Sequence[ExternalEvent],
    max_records: int,
) -> np.ndarray:
    """Lower a projected/filtered EventTrace (the output of
    subsequence_intersection) into replay records [max_records, rec_width].

    External Send payloads are re-bound via their constructors first, and
    the corresponding delivery records carry the re-bound payload (uid
    linkage), so payload shrinking composes with device replay."""
    _uids, rows, _mask = lower_expected_matrix(app, cfg, trace, externals)
    return _pack_matrix(cfg, rows, max_records)


class CandidateLowerer:
    """Lower-once/gather-many candidate lowering (the async-minimization
    pipeline's host-side hot-path fix): ddmin and internal-minimization
    candidates are event-subsequences of one base trace, so the base is
    lowered to per-event rows ONCE and each candidate materializes as a
    NumPy row-gather instead of a fresh ``lower_expected_trace`` Python
    loop. Soundness rests on ``lower_expected_matrix``: a surviving event's
    row depends only on the event (and its own Send's payload), and
    subsequence projection / delivery removal reuse the base trace's
    ``Unique`` objects, so gathered rows equal a from-scratch lowering
    byte-for-byte (pinned by tests/test_async_min.py).

    Two LRU layers: ``bases`` (uid -> row-index maps + the packed row
    matrix) and ``candidates`` keyed by (base token, surviving-uid tuple)
    — equivalently the (trace id, removed-index set) of the level that
    produced the candidate. Unknown uids (wildcarded deliveries get fresh
    Uniques, host re-executions renumber) fall back to a full lowering,
    which is then registered as a new base so the NEXT round's candidates
    gather again."""

    def __init__(
        self,
        app: DSLApp,
        cfg: DeviceConfig,
        max_records: int,
        base_capacity: int = 8,
        candidate_capacity: int = 256,
    ):
        self.app = app
        self.cfg = cfg
        self.max_records = max_records
        self.base_capacity = base_capacity
        self.candidate_capacity = candidate_capacity
        self._bases: "OrderedDict[int, dict]" = OrderedDict()
        self._candidates: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._base_token = 0
        self.stats = {"full": 0, "gathers": 0, "cached": 0, "bases": 0}

    def hit_rate(self) -> float:
        """Fraction of lowerings served without a full Python lowering."""
        served = self.stats["gathers"] + self.stats["cached"]
        total = served + self.stats["full"]
        return served / total if total else 0.0

    def _register_base(self, rows: np.ndarray, row_of, ref) -> int:
        self._base_token += 1
        self._bases[self._base_token] = {
            "rows": rows, "row_of": row_of, "ref": ref,
        }
        self.stats["bases"] += 1
        while len(self._bases) > self.base_capacity:
            self._bases.popitem(last=False)
        return self._base_token

    def register_base(
        self, trace: EventTrace, externals: Sequence[ExternalEvent]
    ) -> None:
        """Explicitly lower+register a base (e.g. a round's baseline or a
        ddmin level's current dag projection) so the level's candidates
        gather instead of full-lowering. Idempotent enough: a base whose
        uid set is already gatherable registers via the gather path."""
        self._lower_impl(trace, externals, register=True)

    def lower(
        self, trace: EventTrace, externals: Sequence[ExternalEvent]
    ) -> Tuple[np.ndarray, bytes]:
        """Lower one candidate; returns (records [max_records, rec_width],
        digest). The digest keys the speculative verdict cache: verdicts
        are a pure function of the record bytes (replay lanes never
        consume rng), so equal digests may share a verdict bit-exactly."""
        return self._lower_impl(trace, externals, register=False)

    def _lower_impl(self, trace, externals, register: bool):
        # Keys are Unique WRAPPER identities, not Unique.id: a MsgSend and
        # its delivery share one uid (the send/delivery linkage), and
        # wildcard minimization rewraps deliveries into fresh events under
        # the same uid — both would alias a uid-keyed map. The base holds
        # references to its wrappers, so a live id() can't be reused and
        # ``ref.get(id(u)) is u`` means exactly "this event, unmodified,
        # is part of the base". Identity misses fall back to a full
        # lowering (correct for wildcarded / re-executed traces).
        keys = tuple(id(u) for u in trace.events)
        for token in reversed(self._bases):
            base = self._bases[token]
            row_of, ref = base["row_of"], base["ref"]
            idx: List[int] = []
            ok = True
            for u in trace.events:
                k = id(u)
                if ref.get(k) is not u:
                    ok = False
                    break
                r = row_of.get(k)
                if r is not None:
                    idx.append(r)
            if ok and len(idx) > 1:
                # Subsequence order check, one vectorized pass: gathered
                # row indices must be strictly increasing.
                arr = np.asarray(idx, np.intp)
                ok = bool((arr[1:] > arr[:-1]).all())
            if not ok:
                continue
            cand_key = (token, keys)
            # register=True must reach the gather path below (the point
            # is to install a new base), so it skips the shortcut.
            hit = None if register else self._candidates.get(cand_key)
            if hit is not None:
                self._candidates.move_to_end(cand_key)
                self.stats["cached"] += 1
                obs.counter("pipe.lower_cached").inc()
                return hit
            if len(idx) > self.max_records:
                raise ValueError(
                    f"expected trace has {len(idx)} records > {self.max_records}"
                )
            rows = base["rows"][np.asarray(idx, np.intp)] if idx else (
                np.zeros((0, self.cfg.rec_width), np.int32)
            )
            out = np.zeros((self.max_records, self.cfg.rec_width), np.int32)
            out[: len(idx)] = rows
            digest = hashlib.blake2b(out.tobytes(), digest_size=16).digest()
            self.stats["gathers"] += 1
            obs.counter("pipe.lower_gather").inc()
            if register:
                new_row_of = {}
                for u in trace.events:
                    if id(u) in row_of:
                        new_row_of[id(u)] = len(new_row_of)
                self._register_base(
                    rows, new_row_of, {id(u): u for u in trace.events}
                )
            self._remember_candidate((token, keys), out, digest)
            return out, digest

        # No base covers this candidate: full lowering, registered as a
        # fresh base so the next round's subsequences gather.
        _uids, rows, has_row = lower_expected_matrix(
            self.app, self.cfg, trace, externals
        )
        out = _pack_matrix(self.cfg, rows, self.max_records)
        digest = hashlib.blake2b(out.tobytes(), digest_size=16).digest()
        self.stats["full"] += 1
        obs.counter("pipe.lower_full").inc()
        row_of: dict = {}
        for u, has in zip(trace.events, has_row):
            if has:
                row_of[id(u)] = len(row_of)
        token = self._register_base(
            out[: len(rows)].copy(), row_of, {id(u): u for u in trace.events}
        )
        self._remember_candidate((token, keys), out, digest)
        return out, digest

    def _remember_candidate(self, key, records, digest) -> None:
        self._candidates[key] = (records, digest)
        self._candidates.move_to_end(key)
        while len(self._candidates) > self.candidate_capacity:
            self._candidates.popitem(last=False)


# ---------------------------------------------------------------------------
# Lifting device explore traces back to host EventTraces
# ---------------------------------------------------------------------------

_NET_STEPS = {REC_KEPT: "keep", REC_DISCARDED: "discard"}


def host_sched_hash(app: DSLApp, trace: EventTrace) -> int:
    """The device's ``sched_hash`` (``core.delivery_effects``' fold) of a
    host trace's delivered sequence, a kept delivery and a discarded
    message with their constants: a lifted lane's host execution gives
    the hash the lane carries exactly when it delivered, kept and
    discarded the same messages in the same order."""
    mod = 1 << 32
    powers = [pow(31, j, mod) for j in range(app.msg_width)]
    h, kept = 0x811C9DC5, False
    for u in trace.events:
        ev = u.event
        if isinstance(ev, MsgKept):
            kept = True  # says so of the MsgEvent that follows
            continue
        if isinstance(ev, TimerDelivery):
            src = dst = app.actor_id(ev.rcv)
            extra = 0xC2B2AE35
        elif isinstance(ev, (MsgEvent, MsgDiscarded)):
            src = _actor_or_external(app, ev.snd)
            dst = app.actor_id(ev.rcv)
            extra = HASH_DISCARDED if isinstance(ev, MsgDiscarded) else (
                HASH_KEPT if kept else 0
            )
            kept = False
        else:
            continue
        row = _msg_row(app, ev.msg, app.msg_width)
        mix = sum((int(x) % mod) * p for x, p in zip(row, powers))
        mix += src * 0x9E3779B1 + dst * 0x85EBCA77 + extra
        h = (h * 0x01000193 + mix) % mod
    return h


def device_trace_to_guide(
    app: DSLApp, records: np.ndarray, trace_len: int
) -> List[Tuple]:
    """Decode a device-recorded trace into a host guide: a list of
    ("ext", op, a, b, msg) / ("deliver", src, dst, msg, is_timer) steps,
    and over datagram channels ("keep", src, dst, msg, False) /
    ("discard", src, dst, msg, False) for a delivery that left its message
    pending and for a message lost undelivered.
    Accepts parent-tracked records (extra trailing column) transparently."""
    guide: List[Tuple] = []
    for i in range(int(trace_len)):
        rec = records[i]
        kind = int(rec[0])
        msg = tuple(int(x) for x in rec[3 : 3 + app.msg_width])
        if kind == REC_NONE:
            continue
        if kind in (REC_DELIVERY, REC_TIMER):
            guide.append(("deliver", int(rec[1]), int(rec[2]), msg, kind == REC_TIMER))
        elif kind in _NET_STEPS:
            guide.append((_NET_STEPS[kind], int(rec[1]), int(rec[2]), msg, False))
        elif kind >= REC_EXT_BASE:
            guide.append(("ext", kind - REC_EXT_BASE, int(rec[1]), int(rec[2]), msg))
    return guide
