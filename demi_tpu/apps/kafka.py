"""Kafka's partition replication as KIP-101 found it, in the dual
host/device DSL: many replication groups laid over one set of brokers,
followers that pull, a controller that elects from an in-sync set kept by
compare-and-set, and a commit point the follower learns one round trip late.

Apache Kafka's design document, sec. 4.7 "Replication" (replicas, the ISR,
"committed" = in every in-sync replica, ``acks=all`` with
``min.insync.replicas``, unclean election off); KIP-101, "Alter Replication
Protocol to use Leader Epoch rather than High Watermark for Truncation"
(0.11.0), whose Motivation gives the two schedules the seeded bug reproduces;
KIP-279, "Fix log divergence between leader and follower after fast leader
fail over" (2.0); KIP-497's rule that the *maximal* ISR bounds the high
watermark while a change is in flight.

Actors ``0 .. n - 2`` are brokers, actor ``n - 1`` the controller (ZooKeeper's
partition-state znodes and the controller's election logic as one actor: the
fault program cuts it off and never kills it, ``DSLApp.unkillable``).
brokers + 1 partitions (6 over 5: every broker replicates 3 or 4, two
brokers stand outside each partition) of replication factor 3, assignment
round-robin from broker 0 as ``kafka-topics --create`` lays it out:
partition p has replicas (p, p + 1, p + 2) mod brokers, the first its
preferred leader. A broker's row holds one *slot* a partition it replicates
(``SLOTS`` = the most any broker holds, at most 4), fields laid out as arrays
over the slots (``state_layout``); every handler finds its slot by the
partition number the message carries. The controller's words follow the
brokers' in the same row (disjoint, so the two roles' handlers share one
``lax.switch`` and no word).

Messages, ``msg_width`` = 2 + 12 x SLOTS (FETCH_RESP):

  T_FETCH (timer)          one FETCH to each broker that leads a partition
                           this one follows in state fetching, entry j =
                           this broker's slot j (p = -1 where it does not
                           ask); a pending OFFSETS_FOR_EPOCH is sent again.
  T_ISR (timer)            ``replica.lag.time.max.ms``: a leader with nothing
                           pending proposes ISR minus the members that have
                           not fetched at its log end through LAG_MISSES
                           T_ISRs on end.
  T_CKPT (timer)           HW_CKPT = HW (``replication-offset-checkpoint``).
  T_HEARTBEAT (timer)      HEARTBEAT to the controller.
  T_SESSION (timer, the    every live broker not heard from through
      controller's)        SESSION_MISSES of them on end has expired
                           (``zookeeper.session.timeout.ms``).
  REGISTER, HEARTBEAT      a broker's session; a heartbeat from an expired
                           broker registers it again.
  LEADER_AND_ISR(p, leader, epoch, isr, zkv)
  ALTER_ISR(p, epoch, isr, zkv) / ALTER_ISR_RESP(p, epoch, ok, isr, zkv)
                           ZooKeeper's conditional update of the ISR.
  FETCH(b, n, [p, offset, epoch] x SLOTS)
  FETCH_RESP(n, [p, err, epoch, hw, base, k, (value, epoch) x 3] x SLOTS)
  OFFSETS_FOR_EPOCH(p, epoch, e) / OFFSETS_FOR_EPOCH_RESP(p, epoch, err, e,
      found_epoch, end_offset)           KIP-101's request, KIP-279's reply.
  PRODUCE(p, value, forwarded)           from the operator; a broker that is
                           not the leader forwards it once.

The rules are ``benchmarks/configs/kafka5-acks-all.json``'s, one paragraph a
message kind; the plain reference (``benchmarks/lib/kafka_reference.py``)
states them again in Python objects.

``DSLApp.durable``: the logs, the epoch caches, the checkpointed high
watermarks and the ghost counts. The live high watermark is *not* durable:
a restarted broker's first delivery sets it from the checkpoint (BOOTED).

Safety invariants, after every delivery, over live brokers a, b and a
partition p both replicate:
  code 1 -- an acknowledged record is lost (KIP-101, scenario 1): a leads p
            in an epoch above EXPOSED_AT_b[p] with a log end below
            EXPOSED_b[p] (a = b allowed).
  code 2 -- replicas diverge below the high watermark (scenario 2, KIP-279):
            an offset below both high watermarks at which the values differ.

Seeded bugs:
  bug="truncate_to_hw"            -- the protocol as shipped through 0.10.2:
                                     a new follower truncates to its own
                                     high watermark and asks nobody.
  bug="epoch_unknown_replies_leo" -- KIP-101 as first shipped: a leader that
                                     does not hold the follower's epoch
                                     answers with its own log end.
"""

from __future__ import annotations

import random as _random
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..dsl import DSLApp
from ..external_events import OP_START, Send, constant_message

# Message tags.
T_FETCH = 1  # timer (broker)
T_ISR = 2  # timer (broker)
T_CKPT = 3  # timer (broker)
T_HEARTBEAT = 4  # timer (broker)
T_SESSION = 5  # timer (controller)
T_REGISTER = 6  # (tag, b)
T_HEARTBEAT_MSG = 7  # (tag, b)
T_LEADER_AND_ISR = 8  # (tag, p, leader, epoch, isr, zkv)
T_ALTER_ISR = 9  # (tag, p, epoch, isr, zkv)
T_ALTER_ISR_RESP = 10  # (tag, p, epoch, ok, isr, zkv)
T_FETCH_REQ = 11  # (tag, b, n, SLOTS x (p, offset, epoch))
T_FETCH_RESP = 12  # (tag, n, SLOTS x (p, err, epoch, hw, base, k, 3 x (value, epoch)))
T_OFFSETS = 13  # (tag, p, epoch, e)
T_OFFSETS_RESP = 14  # (tag, p, epoch, err, e, found_epoch, end_offset)
T_PRODUCE = 15  # (tag, p, value, forwarded)
NUM_TAGS = 15

RF = 3  # replication factor
MIN_ISR = 2  # min.insync.replicas
RECORDS = 3  # records a partition a FETCH_RESP carries
FETCH_ENTRY = 3
RESP_ENTRY = 6 + 2 * RECORDS
MAX_SLOTS = 4  # partitions a FETCH covers
EPOCHS = 8  # entries of a partition's leader-epoch cache
# A session is several heartbeats long (zookeeper.session.timeout.ms 6 s
# over a 2 s tick), a lagging replica many fetches behind
# (replica.lag.time.max.ms 10 s over replica.fetch.wait.max.ms 0.5 s): with
# the scheduler's timers the ratio is a count of the slower timer's
# deliveries during which the faster one's work never arrived.
SESSION_MISSES = 4  # T_SESSIONs without a heartbeat that expire a broker
LAG_MISSES = 8  # T_ISRs without a caught-up fetch that make a replica lag
BUGS = (None, "truncate_to_hw", "epoch_unknown_replies_leo")

NONE, FOLLOWER, LEADER = 0, 1, 2  # ROLE
FETCHING, TRUNCATING = 0, 1  # FSTATE

RESTORES = 0  # DSLApp.spawn_count: 1 in a first life
BOOTED = 1  # this life has handled a delivery (HW is the checkpoint's)

# A slot's scalars, each an array over the slots; the first 13 are durable.
_DURABLE_SLOT = (
    "leo", "hw_ckpt", "ep_len", "exposed", "exposed_at", "acked", "rejected",
    "elected", "isr_shrunk", "isr_grown", "truncated", "fenced",
    "epoch_overflow",
)
_VOLATILE_SLOT = (
    "role", "epoch", "leader", "hw", "fstate", "isr", "zkv", "pend_add",
    "pend_del", "caught",
)
_CONTROLLER = ("c_leader", "c_epoch", "c_isr", "c_zkv")


def assignment(brokers: int, partitions: int):
    """``replicas[p]`` in assignment order, and each broker's partitions in
    slot order."""
    replicas = [[(p + r) % brokers for r in range(RF)] for p in range(partitions)]
    held = [
        [p for p in range(partitions) if b in replicas[p]]
        for b in range(brokers)
    ]
    return replicas, held


def state_layout(num_actors: int, log_cap: int) -> dict:
    """Word offsets of a row: ``{NAME: (start, length)}``; a slot field of
    length SLOTS x k is SLOTS rows of k words."""
    brokers, parts = num_actors - 1, num_actors
    _, held = assignment(brokers, parts)
    slots = max(len(h) for h in held)
    layout = {"RESTORES": (RESTORES, 1), "BOOTED": (BOOTED, 1)}
    at = 2
    fields = [(name, slots) for name in _DURABLE_SLOT + _VOLATILE_SLOT]
    fields += [
        ("log_v", slots * log_cap), ("log_e", slots * log_cap),
        ("ep_e", slots * EPOCHS), ("ep_s", slots * EPOCHS),
        ("f_leo", slots * brokers), ("lag", slots * brokers),
    ]
    fields += [(name, parts) for name in _CONTROLLER]
    fields += [("c_missed", brokers), ("live", 1), ("heard", 1)]
    for name, length in fields:
        layout[name.upper()] = (at, length)
        at += length
    layout["width"] = (at, 0)
    layout["slots"] = (slots, 0)
    return layout


def durable_words(num_actors: int, log_cap: int) -> tuple:
    """What a broker has on disk (the logs, the epoch caches, the
    checkpointed high watermarks) and the ghost counts."""
    lay = state_layout(num_actors, log_cap)
    words = []
    for name in _DURABLE_SLOT + ("log_v", "log_e", "ep_e", "ep_s"):
        start, length = lay[name.upper()]
        words += range(start, start + length)
    return tuple(sorted(words))


def make_kafka_app(
    num_actors: int = 6,
    log_cap: int = 24,
    bug: Optional[str] = None,
    name: str = "k",
) -> DSLApp:
    n, L = num_actors, log_cap
    B = n - 1  # brokers; the controller is actor B
    P = n  # partitions
    if not 3 <= B <= 8:
        raise ValueError("kafka needs 3..8 brokers and a controller (--nodes 4..9)")
    if bug not in BUGS:
        raise ValueError(f"unknown kafka bug {bug!r} (choices: {BUGS[1:]})")
    if not RECORDS <= L <= 1024:
        raise ValueError(f"log_cap must lie in {RECORDS}..1024")
    replicas, held = assignment(B, P)
    S = max(len(h) for h in held)  # slots a broker
    assert S <= MAX_SLOTS  # RF x (B + 1) / B, rounded up
    lay = state_layout(n, L)
    width = lay["width"][0]
    W = 2 + RESP_ENTRY * S
    K = max(P * RF + 1, B + S + 1, 5)

    # Static tables.
    slot_p_np = np.full((n, S), -1, np.int32)
    for b, ps in enumerate(held):
        slot_p_np[b, : len(ps)] = ps
    rep_np = np.asarray(replicas, np.int32)  # [P, RF]
    rep_mask_np = np.asarray(
        [sum(1 << b for b in reps) for reps in replicas], np.int32
    )
    # HOLDS[b, p, s]: slot s of broker b is partition p.
    holds_np = np.zeros((B, P, S), np.int32)
    for b, ps in enumerate(held):
        for s, p in enumerate(ps):
            holds_np[b, p, s] = 1

    ids = jnp.arange(B, dtype=jnp.int32)
    offs = jnp.arange(L, dtype=jnp.int32)
    eks = jnp.arange(EPOCHS, dtype=jnp.int32)
    big = jnp.int32(1 << 30)

    shapes = {name: (S,) for name in _DURABLE_SLOT + _VOLATILE_SLOT}
    shapes.update({
        "log_v": (S, L), "log_e": (S, L), "ep_e": (S, EPOCHS),
        "ep_s": (S, EPOCHS), "f_leo": (S, B), "lag": (S, B),
    })
    shapes.update({name: (P,) for name in _CONTROLLER})
    shapes["c_missed"] = (B,)
    order = (
        _DURABLE_SLOT + _VOLATILE_SLOT
        + ("log_v", "log_e", "ep_e", "ep_s", "f_leo", "lag") + _CONTROLLER
        + ("c_missed",)
    )

    def init_state(actor_id: int) -> np.ndarray:
        s = np.zeros(width, np.int32)
        for key in ("EPOCH", "LEADER", "C_LEADER"):
            start, length = lay[key]
            s[start : start + length] = -1
        start, length = lay["C_ISR"]
        s[start : start + length] = rep_mask_np
        return s

    def initial_msgs(actor_id: int) -> np.ndarray:
        if actor_id == B:
            rows = np.zeros((1, 2 + W), np.int32)
            rows[0, :3] = (1, B, T_SESSION)
            return rows
        rows = np.zeros((5, 2 + W), np.int32)
        rows[0, :4] = (1, B, T_REGISTER, actor_id)
        for k, tag in enumerate((T_FETCH, T_ISR, T_CKPT, T_HEARTBEAT)):
            rows[1 + k, :3] = (1, actor_id, tag)
        return rows

    # -- the row as a dict of arrays ---------------------------------------
    def unpack(state):
        s = {"restores": state[RESTORES], "booted": state[BOOTED]}
        for key in order:
            start, length = lay[key.upper()]
            s[key] = state[start : start + length].reshape(shapes[key])
        s["live"] = state[lay["LIVE"][0]]
        s["heard"] = state[lay["HEARD"][0]]
        return s

    def pack(s):
        parts = [jnp.stack([s["restores"], s["booted"]]).astype(jnp.int32)]
        parts += [s[key].reshape(-1).astype(jnp.int32) for key in order]
        parts.append(jnp.stack([s["live"], s["heard"]]).astype(jnp.int32))
        return jnp.concatenate(parts)

    def bit(i):
        return jnp.int32(1) << i

    def has(mask, i):
        return ((mask >> i) & 1) == 1

    def popcount(mask):
        """Members of each mask of a vector of masks."""
        return jnp.sum((mask[:, None] >> ids[None, :]) & 1, axis=1)

    def pick(hit, vec):
        """The value of ``vec`` in the one slot ``hit`` names (0 if none)."""
        return jnp.sum(jnp.where(hit, vec, 0))

    def last_epoch(s):
        """The epoch of each slot's last entry (-1: an empty log)."""
        at = offs[None, :] == (s["leo"] - 1)[:, None]
        return jnp.where(
            s["leo"] > 0, jnp.sum(jnp.where(at, s["log_e"], 0), axis=1), -1
        )

    def truncate(s, mask, new_leo):
        """LEO = min(LEO, new_leo) in the slots of ``mask``; the epoch
        cache is cut to match (an entry that starts at or past the new end
        holds no record), the high watermark never stands past the end."""
        leo = jnp.where(mask, jnp.minimum(s["leo"], new_leo), s["leo"])
        gone = mask[:, None] & (offs[None, :] >= leo[:, None])
        valid = eks[None, :] < s["ep_len"][:, None]
        ep_len = jnp.where(
            mask, jnp.sum(valid & (s["ep_s"] < leo[:, None]), axis=1),
            s["ep_len"],
        )
        cut = mask[:, None] & (eks[None, :] >= ep_len[:, None])
        return {
            **s, "leo": leo, "ep_len": ep_len,
            "log_v": jnp.where(gone, 0, s["log_v"]),
            "log_e": jnp.where(gone, 0, s["log_e"]),
            "ep_e": jnp.where(cut, 0, s["ep_e"]),
            "ep_s": jnp.where(cut, 0, s["ep_s"]),
            "truncated": s["truncated"] + (s["leo"] - leo),
            "hw": jnp.where(mask, jnp.minimum(s["hw"], leo), s["hw"]),
        }

    def assign_epoch(s, mask, epoch, start):
        """``leader-epoch-checkpoint``: (epoch, start) appended in the slots
        of ``mask`` whose cache ends in an older epoch; an entry that would
        start at or past it goes first (it holds no record); a full cache
        drops its oldest entry and counts."""
        valid = eks[None, :] < s["ep_len"][:, None]
        newest = jnp.max(jnp.where(valid, s["ep_e"], -1), axis=1)
        need = mask & (epoch > newest)
        kept = jnp.sum(valid & (s["ep_s"] < start[:, None]), axis=1)
        full = need & (kept >= EPOCHS)
        zero = jnp.zeros((S, 1), jnp.int32)
        ep_e = jnp.where(
            full[:, None], jnp.concatenate([s["ep_e"][:, 1:], zero], 1), s["ep_e"]
        )
        ep_s = jnp.where(
            full[:, None], jnp.concatenate([s["ep_s"][:, 1:], zero], 1), s["ep_s"]
        )
        at = jnp.where(full, EPOCHS - 1, kept)
        here = need[:, None] & (eks[None, :] == at[:, None])
        past = need[:, None] & (eks[None, :] > at[:, None])
        return {
            **s,
            "ep_e": jnp.where(here, epoch[:, None], jnp.where(past, 0, ep_e)),
            "ep_s": jnp.where(here, start[:, None], jnp.where(past, 0, ep_s)),
            "ep_len": jnp.where(need, at + 1, s["ep_len"]),
            "epoch_overflow": s["epoch_overflow"] + full.astype(jnp.int32),
        }

    def append(s, mask, value, epoch):
        """One record at each masked slot's end (the caller has looked for
        room)."""
        at = mask[:, None] & (offs[None, :] == s["leo"][:, None])
        s = assign_epoch(s, mask, epoch, s["leo"])
        return {
            **s,
            "log_v": jnp.where(at, value[:, None], s["log_v"]),
            "log_e": jnp.where(at, epoch[:, None], s["log_e"]),
            "leo": s["leo"] + mask.astype(jnp.int32),
        }

    def advance_hw(actor_id, s):
        """Design 4.7 with KIP-497: a leader's high watermark is the least
        fetch offset over the maximal ISR (a replica being added already
        bounds it, one being removed still does); it never falls."""
        lead = s["role"] == LEADER
        maximal = s["isr"] | s["pend_add"]
        ends = jnp.where(ids[None, :] == actor_id, s["leo"][:, None], s["f_leo"])
        low = jnp.min(
            jnp.where(has(maximal[:, None], ids[None, :]), ends, big), axis=1
        )
        hw = jnp.where(
            lead & (maximal != 0),
            jnp.maximum(s["hw"], jnp.minimum(low, s["leo"])), s["hw"],
        )
        up = lead & (hw > s["exposed"])
        return {
            **s, "hw": hw,
            "acked": s["acked"] + jnp.where(
                popcount(s["isr"]) >= MIN_ISR, hw - s["hw"], 0
            ),
            "exposed": jnp.where(up, hw, s["exposed"]),
            "exposed_at": jnp.where(up, s["epoch"], s["exposed_at"]),
        }

    def recover(s):
        """The first delivery of a life: the high watermark is the
        checkpoint's (memory's is gone), never past the log's end."""
        fresh = s["booted"] == 0
        return {
            **s, "booted": jnp.int32(1),
            "hw": jnp.where(fresh, jnp.minimum(s["hw_ckpt"], s["leo"]), s["hw"]),
        }

    # -- the outbox --------------------------------------------------------
    def rows_of(valid, dst, *fields):
        """``[k, 2 + W]`` rows from columns (scalars broadcast)."""
        k = valid.shape[0]
        cols = [
            jnp.broadcast_to(jnp.asarray(x, jnp.int32), (k,))
            for x in (valid, dst) + fields
        ]
        head = jnp.stack(cols, axis=1)
        head = jnp.where(valid[:, None], head, 0)
        return jnp.concatenate(
            [head, jnp.zeros((k, 2 + W - head.shape[1]), jnp.int32)], axis=1
        )

    def wide(valid, dst, body):
        """Rows whose message is ``body`` ``[k, w]`` whole."""
        k = valid.shape[0]
        head = jnp.stack([
            jnp.broadcast_to(jnp.asarray(x, jnp.int32), (k,))
            for x in (valid, dst)
        ], axis=1)
        rows = jnp.concatenate([
            head, body.astype(jnp.int32),
            jnp.zeros((k, W - body.shape[1]), jnp.int32),
        ], axis=1)
        return jnp.where(valid[:, None], rows, 0)

    def outbox(*blocks):
        rows = jnp.concatenate(blocks, axis=0)
        return jnp.concatenate(
            [rows, jnp.zeros((K - rows.shape[0], 2 + W), jnp.int32)], axis=0
        )

    def one(valid, dst, *fields):
        return rows_of(jnp.asarray(valid).reshape(1), dst, *fields)

    EMPTY = jnp.zeros((K, 2 + W), jnp.int32)

    # -- a broker's handlers: (actor_id, s, snd, msg, my_p) -> (s, outbox) --
    def on_fetch_timer(me, s, snd, msg, my_p):
        follows = (my_p >= 0) & (s["role"] == FOLLOWER) & (s["leader"] >= 0)
        asks = follows & (s["fstate"] == FETCHING)
        to = asks[None, :] & (s["leader"][None, :] == ids[:, None])  # [B, S]
        entry = jnp.stack([my_p, s["leo"], s["epoch"]], axis=1)  # [S, 3]
        absent = jnp.asarray([-1, 0, 0], jnp.int32)
        entries = jnp.where(to[:, :, None], entry[None], absent[None, None])
        count = jnp.sum(to, axis=1)
        body = jnp.concatenate([
            jnp.stack([
                jnp.full(B, T_FETCH_REQ, jnp.int32),
                jnp.broadcast_to(jnp.asarray(me, jnp.int32), (B,)), count,
            ], axis=1),
            entries.reshape(B, FETCH_ENTRY * S),
        ], axis=1)
        waits = follows & (s["fstate"] == TRUNCATING) & (s["leo"] > 0)
        return s, outbox(
            wide(count > 0, ids, body),
            rows_of(waits, jnp.maximum(s["leader"], 0), T_OFFSETS, my_p,
                    s["epoch"], last_epoch(s)),
            one(True, me, T_FETCH),
        )

    def on_isr_timer(me, s, snd, msg, my_p):
        lead = s["role"] == LEADER
        idle = (s["pend_add"] == 0) & (s["pend_del"] == 0)
        behind = ~has(s["caught"][:, None], ids[None, :])
        lag = jnp.where(
            lead[:, None], jnp.where(behind, s["lag"] + 1, 0), s["lag"]
        )
        late = (lag >= LAG_MISSES) & (ids[None, :] != me)
        lagging = s["isr"] & jnp.sum(jnp.where(late, bit(ids)[None, :], 0), axis=1)
        shrink = lead & idle & (lagging != 0)
        out = outbox(
            rows_of(shrink, B, T_ALTER_ISR, my_p, s["epoch"],
                    s["isr"] & ~lagging, s["zkv"]),
            one(True, me, T_ISR),
        )
        return {
            **s, "pend_del": jnp.where(shrink, lagging, s["pend_del"]),
            "caught": jnp.where(lead, 0, s["caught"]), "lag": lag,
        }, out

    def on_ckpt_timer(me, s, snd, msg, my_p):
        return {**s, "hw_ckpt": s["hw"]}, outbox(one(True, me, T_CKPT))

    def on_heartbeat_timer(me, s, snd, msg, my_p):
        return s, outbox(
            one(True, B, T_HEARTBEAT_MSG, me), one(True, me, T_HEARTBEAT)
        )

    def on_leader_and_isr(me, s, snd, msg, my_p):
        p, leader, epoch, isr, zkv = msg[1], msg[2], msg[3], msg[4], msg[5]
        hit = (my_p == p) & (p >= 0) & (epoch > s["epoch"])
        lead = hit & (leader == me)
        follow = hit & (leader != me)
        followed = (s["role"] == FOLLOWER) & (s["leader"] == leader)
        asks = follow & (leader >= 0)
        if bug == "truncate_to_hw":
            # BUG (0.10.2): the new follower cuts its log to its own high
            # watermark; one that follows this leader already goes on.
            cut = asks & ~followed
            s = truncate(s, cut, s["hw"])
            fstate = jnp.where(cut, FETCHING, s["fstate"])
            sends = jnp.zeros_like(asks)
        else:
            sends = asks & (s["leo"] > 0)
            fstate = jnp.where(
                asks, jnp.where(sends, TRUNCATING, FETCHING), s["fstate"]
            )
        fstate = jnp.where(follow & (leader < 0), TRUNCATING, fstate)
        s = assign_epoch(s, lead, jnp.broadcast_to(epoch, (S,)), s["leo"])
        s = {
            **s,
            "role": jnp.where(lead, LEADER, jnp.where(follow, FOLLOWER, s["role"])),
            "epoch": jnp.where(hit, epoch, s["epoch"]),
            "leader": jnp.where(hit, leader, s["leader"]),
            "fstate": jnp.where(lead, FETCHING, fstate),
            "isr": jnp.where(lead, isr, s["isr"]),
            "zkv": jnp.where(lead, zkv, s["zkv"]),
            "pend_add": jnp.where(hit, 0, s["pend_add"]),
            "pend_del": jnp.where(hit, 0, s["pend_del"]),
            "caught": jnp.where(hit, 0, s["caught"]),
            "f_leo": jnp.where(hit[:, None], 0, s["f_leo"]),
            "lag": jnp.where(hit[:, None], 0, s["lag"]),
            "elected": s["elected"] + lead.astype(jnp.int32),
        }
        return s, outbox(rows_of(
            sends, jnp.maximum(leader, 0), T_OFFSETS, p, epoch, last_epoch(s)
        ))

    def on_alter_isr_resp(me, s, snd, msg, my_p):
        p, epoch, ok, isr, zkv = msg[1], msg[2], msg[3], msg[4], msg[5]
        hit = (
            (my_p == p) & (p >= 0) & (s["role"] == LEADER)
            & (s["epoch"] == epoch)
        )
        took = hit & (ok != 0)
        return {
            **s,
            "isr_grown": s["isr_grown"] + (took & ((isr & ~s["isr"]) != 0)),
            "isr_shrunk": s["isr_shrunk"] + (took & ((s["isr"] & ~isr) != 0)),
            "isr": jnp.where(took, isr, s["isr"]),
            "zkv": jnp.where(took, zkv, s["zkv"]),
            "pend_add": jnp.where(hit, 0, s["pend_add"]),
            "pend_del": jnp.where(hit, 0, s["pend_del"]),
        }, EMPTY

    def on_fetch(me, s, snd, msg, my_p):
        b = jnp.clip(msg[1], 0, B - 1)
        req = msg[3 : 3 + FETCH_ENTRY * S].reshape(S, FETCH_ENTRY)
        p, offset, epoch = req[:, 0], req[:, 1], req[:, 2]
        asked = p >= 0
        # match[j, s]: the request's entry j is this broker's slot s.
        match = asked[:, None] & (p[:, None] == my_p[None, :])
        led = (s["role"] == LEADER)[None, :] & (s["epoch"][None, :] == epoch[:, None])
        good = match & led
        upd = jnp.any(good, axis=0)
        off = jnp.sum(jnp.where(good, offset[:, None], 0), axis=0)
        s = {
            **s,
            "fenced": s["fenced"] + jnp.any(match & ~led, axis=0),
            "f_leo": jnp.where(
                upd[:, None] & (ids[None, :] == b), off[:, None], s["f_leo"]
            ),
            "caught": jnp.where(
                upd & (off >= s["leo"]), s["caught"] | bit(b), s["caught"]
            ),
        }
        # Design 4.7, Partition.maybeExpandIsr: caught up to the high
        # watermark, and nothing else in flight.
        expand = (
            upd & ~has(s["isr"] | s["pend_add"], b) & (off >= s["hw"])
            & (s["pend_add"] == 0) & (s["pend_del"] == 0)
        )
        s = {**s, "pend_add": jnp.where(expand, bit(b), s["pend_add"])}
        s = advance_hw(me, s)
        ok = jnp.any(good, axis=1)  # [S] by entry
        sel = lambda vec: jnp.sum(jnp.where(good, vec[None, :], 0), axis=1)  # noqa: E731
        leo_j, hw_j = sel(s["leo"]), sel(s["hw"])
        count = jnp.where(ok, jnp.clip(leo_j - offset, 0, RECORDS), 0)
        log_v = jnp.sum(jnp.where(good[:, :, None], s["log_v"][None], 0), axis=1)
        log_e = jnp.sum(jnp.where(good[:, :, None], s["log_e"][None], 0), axis=1)
        records = []
        for r in range(RECORDS):
            at = (offs[None, :] == (offset + r)[:, None]) & (r < count)[:, None]
            records += [
                jnp.sum(jnp.where(at, log_v, 0), axis=1),
                jnp.sum(jnp.where(at, log_e, 0), axis=1),
            ]
        entries = jnp.stack([
            jnp.where(asked, p, -1), (asked & ~ok).astype(jnp.int32),
            jnp.where(ok, epoch, 0), hw_j, jnp.where(ok, offset, 0), count,
        ] + records, axis=1)
        body = jnp.concatenate([
            jnp.stack([jnp.int32(T_FETCH_RESP), jnp.sum(asked)]),
            entries.reshape(-1),
        ])[None, :]
        return s, outbox(
            wide(jnp.any(asked).reshape(1), b, body),
            rows_of(expand, B, T_ALTER_ISR, my_p, s["epoch"],
                    s["isr"] | s["pend_add"], s["zkv"]),
        )

    def on_fetch_resp(me, s, snd, msg, my_p):
        resp = msg[2 : 2 + RESP_ENTRY * S].reshape(S, RESP_ENTRY)
        p, err, epoch, hw, base, count = (resp[:, k] for k in range(6))
        take = (
            (p >= 0) & (p == my_p) & (err == 0) & (s["role"] == FOLLOWER)
            & (s["leader"] == snd) & (s["epoch"] == epoch)
            & (s["fstate"] == FETCHING) & (base == s["leo"])
        )
        for r in range(RECORDS):
            fits = take & (r < count) & (s["leo"] < L)
            s = append(s, fits, resp[:, 6 + 2 * r], resp[:, 7 + 2 * r])
        # KIP-101, Motivation: the follower's high watermark is what the
        # leader's was when it answered: one round trip behind its log.
        return {
            **s, "hw": jnp.where(take, jnp.minimum(s["leo"], hw), s["hw"]),
        }, EMPTY

    def on_offsets(me, s, snd, msg, my_p):
        p, epoch, e = msg[1], msg[2], msg[3]
        hit = (my_p == p) & (p >= 0)
        ok = jnp.any(hit & (s["role"] == LEADER) & (s["epoch"] == epoch))
        row = lambda mat: jnp.sum(jnp.where(hit[:, None], mat, 0), axis=0)  # noqa: E731
        ep_e, ep_s = row(s["ep_e"]), row(s["ep_s"])
        valid = eks < pick(hit, s["ep_len"])
        leo = pick(hit, s["leo"])
        # KIP-279: the largest epoch the leader holds at or below e, and
        # where the next one starts.
        found = jnp.max(jnp.where(valid & (ep_e <= e), ep_e, -1))
        end = jnp.min(jnp.where(valid & (ep_e > e), ep_s, leo))
        if bug == "epoch_unknown_replies_leo":
            # BUG (KIP-101 as shipped): an epoch it does not hold is
            # answered with its own log end.
            known = jnp.any(valid & (ep_e == e))
            found = jnp.where(known, found, e)
            end = jnp.where(known, end, leo)
        return s, outbox(one(
            True, jnp.clip(snd, 0, B - 1), T_OFFSETS_RESP, p, epoch,
            (~ok).astype(jnp.int32), e, jnp.where(ok, found, -1),
            jnp.where(ok, end, -1),
        ))

    def on_offsets_resp(me, s, snd, msg, my_p):
        p, epoch, err, e, found, end = (msg[k] for k in range(1, 7))
        mine = last_epoch(s)
        hit = (
            (my_p == p) & (p >= 0) & (s["role"] == FOLLOWER)
            & (s["fstate"] == TRUNCATING) & (s["leader"] == snd)
            & (s["epoch"] == epoch) & (err == 0) & (mine == e)
        )
        # Entries of an epoch above the one found go (epochs never fall
        # along a log, so what is left is a prefix).
        left = jnp.sum(
            (offs[None, :] < s["leo"][:, None]) & (s["log_e"] <= found), axis=1
        )
        s = truncate(s, hit, jnp.where(found == e, end, jnp.minimum(left, end)))
        again = hit & (found != e) & (s["leo"] > 0)
        s = {**s, "fstate": jnp.where(hit & ~again, FETCHING, s["fstate"])}
        return s, outbox(rows_of(
            again, jnp.clip(snd, 0, B - 1), T_OFFSETS, p, epoch, last_epoch(s)
        ))

    def on_produce(me, s, snd, msg, my_p):
        p, value, forwarded = msg[1], msg[2], msg[3]
        hit = (my_p == p) & (p >= 0)
        lead = hit & (s["role"] == LEADER)
        ok = lead & (popcount(s["isr"]) >= MIN_ISR) & (s["leo"] < L)
        s = append(s, ok, jnp.broadcast_to(value, (S,)), s["epoch"])
        s = {**s, "rejected": s["rejected"] + (lead & ~ok)}
        # The producer's NOT_LEADER, metadata refresh and one retry.
        via = (
            hit & (s["role"] == FOLLOWER) & (s["leader"] >= 0)
            & (s["leader"] != me) & (forwarded == 0)
        )
        return s, outbox(one(
            jnp.any(via), jnp.clip(pick(via, s["leader"]), 0, B - 1),
            T_PRODUCE, p, value, 1,
        ))

    # -- the controller's handlers -----------------------------------------
    rep = jnp.asarray(rep_np)
    rep_mask = jnp.asarray(rep_mask_np)
    ranks = jnp.arange(RF, dtype=jnp.int32)

    def expire(s, gone):
        """The controller's OfflineReplica transition for the brokers of
        ``gone`` (design 4.7): out of each ISR that has another member
        (the last one stays), and a partition one of them led goes to the
        first live member of its ISR in assignment order, or offline
        (``unclean.leader.election.enable=false``). What changed."""
        live = s["live"] & ~gone
        isr = s["c_isr"]
        lost = isr & gone
        left = isr & ~gone
        top = jnp.max(
            jnp.where(has(isr[:, None], ids[None, :]), bit(ids)[None, :], 0),
            axis=1,
        )
        new_isr = jnp.where(left != 0, left, jnp.where(lost != 0, top, isr))
        led = s["c_leader"]
        leaderless = (led >= 0) & has(gone, jnp.maximum(led, 0))
        able = has(live, rep) & has(new_isr[:, None], rep)  # [P, RF]
        first = jnp.min(jnp.where(able, ranks[None, :], RF), axis=1)
        heir = jnp.sum(jnp.where(ranks[None, :] == first[:, None], rep, 0), axis=1)
        heir = jnp.where(first < RF, heir, -1)
        changed = (new_isr != isr) | leaderless
        return {
            **s, "live": live, "c_isr": new_isr,
            "c_leader": jnp.where(leaderless, heir, led),
            "c_epoch": s["c_epoch"] + changed,
            "c_zkv": s["c_zkv"] + changed,
        }, changed

    def state_rows(s, to):
        """LEADER_AND_ISR of partition p to its replica of rank r, row
        p x RF + r, where ``to[p, r]`` and the replica is live."""
        valid = to & has(s["live"], rep)
        col = lambda vec: jnp.broadcast_to(vec[:, None], (P, RF)).reshape(-1)  # noqa: E731
        return rows_of(
            valid.reshape(-1), rep.reshape(-1), T_LEADER_AND_ISR,
            col(jnp.arange(P, dtype=jnp.int32)), col(s["c_leader"]),
            col(s["c_epoch"]), col(s["c_isr"]), col(s["c_zkv"]),
        )

    def on_hello(me, s, snd, msg, my_p):
        """REGISTER, and HEARTBEAT (which is one from a broker the
        controller holds expired)."""
        b = jnp.clip(snd, 0, B - 1)
        known = has(s["live"], b)
        bounce = (msg[0] == T_REGISTER) & known
        # A bounced broker's old session is gone first: it never leads on
        # in the epoch it led in.
        s, changed = expire(s, jnp.where(bounce, bit(b), 0))
        joins = bounce | ~known
        mine = has(rep_mask, b)
        takes = joins & mine & (s["c_leader"] < 0) & has(s["c_isr"], b)
        s = {
            **s,
            "live": s["live"] | jnp.where(joins, bit(b), 0),
            "heard": s["heard"] | bit(b),
            "c_missed": jnp.where(ids == b, 0, s["c_missed"]),
            "c_leader": jnp.where(takes, b, s["c_leader"]),
            "c_epoch": s["c_epoch"] + takes,
            "c_zkv": s["c_zkv"] + takes,
        }
        to = (changed | takes)[:, None] | (joins & mine[:, None] & (rep == b))
        return s, outbox(state_rows(s, to))

    def on_session_timer(me, s, snd, msg, my_p):
        silent = has(s["live"] & ~s["heard"], ids)
        missed = jnp.where(silent, s["c_missed"] + 1, 0)
        out_of_time = missed >= SESSION_MISSES
        s, changed = expire(s, jnp.sum(jnp.where(out_of_time, bit(ids), 0)))
        s = {
            **s, "heard": jnp.int32(0),
            "c_missed": jnp.where(out_of_time, 0, missed),
        }
        return s, outbox(
            state_rows(s, jnp.broadcast_to(changed[:, None], (P, RF))),
            one(True, B, T_SESSION),
        )

    def on_alter_isr(me, s, snd, msg, my_p):
        p, epoch, isr, zkv = msg[1], msg[2], msg[3], msg[4]
        at = jnp.arange(P, dtype=jnp.int32) == p
        ok = jnp.any(
            at & (s["c_leader"] == snd) & (s["c_epoch"] == epoch)
            & (s["c_zkv"] == zkv)
        )
        s = {
            **s, "c_isr": jnp.where(at & ok, isr, s["c_isr"]),
            "c_zkv": s["c_zkv"] + (at & ok),
        }
        return s, outbox(one(
            True, jnp.clip(snd, 0, B - 1), T_ALTER_ISR_RESP, p, epoch, ok,
            pick(at, s["c_isr"]), pick(at, s["c_zkv"]),
        ))

    # One branch a tag, but REGISTER and HEARTBEAT share theirs (under
    # vmap every branch runs at every step).
    branches = [
        on_fetch_timer, on_isr_timer, on_ckpt_timer, on_heartbeat_timer,
        on_session_timer, on_hello, on_leader_and_isr, on_alter_isr,
        on_alter_isr_resp, on_fetch, on_fetch_resp, on_offsets,
        on_offsets_resp, on_produce,
    ]
    slot_p = jnp.asarray(slot_p_np + 1)

    def handler(actor_id, state, snd, msg):
        s = recover(unpack(state))
        my_p = jnp.sum(
            jnp.where(jnp.arange(n)[:, None] == actor_id, slot_p, 0), axis=0
        ) - 1
        tag = jnp.clip(msg[0], 1, NUM_TAGS)
        branch = tag - 1 - (tag >= T_HEARTBEAT_MSG)
        s, out = jax.lax.switch(branch, branches, actor_id, s, snd, msg, my_p)
        return pack(advance_hw(actor_id, s)), out

    # -- invariants --------------------------------------------------------
    holds = jnp.asarray(holds_np)  # [B, P, S]
    replicates = jnp.asarray(holds_np.sum(axis=2) > 0)  # [B, P]

    def by_partition(states, key):
        """A slot field of the brokers' rows as ``[B, P]`` (``[B, P, L]``
        for a log): 0 where a broker does not replicate p."""
        start, length = lay[key.upper()]
        field = states[:B, start : start + length].reshape((B,) + shapes[key])
        if field.ndim == 2:
            return jnp.sum(holds * field[:, None, :], axis=2)
        return jnp.sum(holds[:, :, :, None] * field[:, None, :, :], axis=2)

    def invariant(states, alive):
        up = alive[:B, None] & replicates  # [B, P]
        role, epoch, leo, hw, exposed, exposed_at = (
            by_partition(states, key)
            for key in ("role", "epoch", "leo", "hw", "exposed", "exposed_at")
        )
        pair = up[:, None, :] & up[None, :, :]  # [a, b, P]
        lost = jnp.any(
            pair & (role == LEADER)[:, None, :]
            & (epoch[:, None, :] > exposed_at[None, :, :])
            & (leo[:, None, :] < exposed[None, :, :])
        )
        log = by_partition(states, "log_v")  # [B, P, L]
        seen = jnp.minimum(hw, leo)
        both = jnp.minimum(seen[:, None, :], seen[None, :, :])
        parted = jnp.any(
            pair[:, :, :, None] & (offs[None, None, None, :] < both[:, :, :, None])
            & (log[:, None, :, :] != log[None, :, :, :])
        )
        return jnp.where(lost, jnp.int32(1), jnp.where(parted, jnp.int32(2), 0))

    def total(*keys):
        def count(states):
            out = jnp.int32(0)
            for key in keys:
                start, length = lay[key.upper()]
                out = out + jnp.sum(states[:B, start : start + length])
            return out
        return count

    progress = (
        ("committed", total("acked")),
        ("elections", total("elected")),
        ("isr_changes", total("isr_shrunk", "isr_grown")),
        ("truncated", total("truncated")),
        ("fenced", total("fenced")),
        ("restores", lambda st: jnp.sum(jnp.maximum(st[:B, RESTORES] - 1, 0))),
    )

    return DSLApp(
        name=name,
        num_actors=n,
        state_width=width,
        msg_width=W,
        max_outbox=K,
        init_state=init_state,
        handler=handler,
        initial_msgs=initial_msgs,
        invariant=invariant,
        timer_tags=(T_FETCH, T_ISR, T_CKPT, T_HEARTBEAT, T_SESSION),
        tag_names=(
            "", "FetchTimer", "IsrTimer", "CheckpointTimer", "HeartbeatTimer",
            "SessionTimer", "Register", "Heartbeat", "LeaderAndIsr",
            "AlterIsr", "AlterIsrResp", "Fetch", "FetchResp",
            "OffsetsForEpoch", "OffsetsForEpochResp", "Produce",
        ),
        durable=durable_words(n, L),
        spawn_count=RESTORES,
        progress=progress,
        channels="fifo",
        unkillable=(B,),
    )


class ProduceOperator:
    """The cluster's one producer, as the fuzzer's send generator: each
    send is PRODUCE(p, value) with p uniform over the partitions and value
    the send's number, to the replica of p it believes leads: the first in
    assignment order it believes up (a client's cached metadata, which may
    be wrong: the broker forwards once, and a record that finds no leader
    is lost unacknowledged, as a producer whose retries ran out loses it).
    ``note_fault`` tells it who is down; it sees no reply."""

    def __init__(self, app: DSLApp):
        self.app = app
        self.partitions = app.num_actors
        self.replicas, _ = assignment(app.num_actors - 1, self.partitions)
        self.reset()

    def reset(self) -> None:
        self.down: set = set()
        self.sends = 0

    def note_fault(self, op: int, name: str) -> None:
        if op == OP_START:
            self.down.discard(name)
        else:
            self.down.add(name)

    def generate_row(self, rng: _random.Random, alive):
        p = rng.randrange(self.partitions)
        names = [self.app.actor_name(b) for b in self.replicas[p]]
        up = [name for name in names if name not in self.down]
        if not up:
            return None  # every replica of p is down: nobody to speak to
        self.sends += 1
        pad = (0,) * (self.app.msg_width - 4)
        return up[0], (T_PRODUCE, p, self.sends, 0) + pad

    def generate(self, rng: _random.Random, alive):
        row = self.generate_row(rng, alive)
        return None if row is None else Send(row[0], constant_message(row[1]))


def kafka_send_generator(app: DSLApp) -> ProduceOperator:
    return ProduceOperator(app)
