"""Chain replication, with its failure repairs, over FIFO links; and the
small read-path fixture the differential tests keep.

van Renesse & Schneider, *Chain Replication for Supporting High Throughput
and Availability* (OSDI 2004), sec. 3: t servers in a chain, updates enter
at the head, travel down reliable FIFO links, the tail replies; server i
keeps ``Hist_i`` and ``Sent_i`` (what it forwarded and has not seen
acknowledged), acknowledgements travel back up. Servers are fail-stop and
a master that never fails repairs the chain: failure of the head, of the
tail, of a middle server (its predecessor resends ``Sent``), and a new
tail that is first brought up to date. ``make_chain_app(t, log_cap=L)``
builds it (``channels="fifo"``: the protocol has no reordering buffer and
is wrong over any other network); ``make_chain_app(t)`` without a
``log_cap`` is the fixture that was here before (below).

**The master is the environment**: ``chain_send_generator`` (the fuzzer's
send generator) keeps the chain's order, is told of every kill and
restart the fuzzer draws (``note_fault``), and while its picture of the
chain differs from what it has told the servers, a send it is asked for is
the one reconfiguration message that brings them closer; else it is the
client's next update to the head. No timer, no failure detector, no actor
that must not be killed. A configuration message carries the master's
epoch (1, 2, ... over all it sends), because this master cannot wait for
an answer before it speaks to the next server.

A message is ``(tag, a, b)``. A server's state is 15 words and its
history, ``state_width`` = 15 + L: STATUS (OUT, CATCHING_UP, MEMBER),
IS_HEAD, IS_TAIL, PRED, SUCC (-1: none), OPN (entries in Hist), ACKED
(``Sent`` is the suffix (ACKED, OPN] of Hist), TARGET, SPAWNS (the
runtime's count of this server's lives, ``DSLApp.spawn_count``), the ghost
counts RESENT_ROWS and RECONFIGS (``DSLApp.durable``), AWAKE,
LINKED (the link from PRED is set up: its CATCHUP has arrived), SUCC_EPOCH
(the epoch of what last set the SUCC side), OWES (it owes SUCC a CATCHUP),
HIST[L] (an entry is the
update's value, its sequence number its index).

  UPDATE(value)      external. MEMBER head, room, value not in Hist:
                     append; a tail too: ACKED = OPN; else FWD to SUCC.
  FWD(n, value)      from PRED, link set up. n <= OPN: dropped (a resend
                     it holds). Else written at n, OPN = n (n > OPN + 1
                     cannot happen over a FIFO link: that it is applied as
                     written is what shows a seeded bug, or a network that
                     reorders). CATCHING_UP and OPN >= TARGET: MEMBER. A
                     MEMBER tail: ACKED = OPN, ACK(OPN) to PRED; not a
                     tail: FWD on to SUCC.
  ACK(n)             from SUCC. ACKED = max(ACKED, min(n, OPN)); not the
                     head: ACK(n) to PRED.
  BECOME_HEAD(e)     master. IS_HEAD, no PRED.
  BECOME_TAIL(e)     master; only if e is newer than SUCC_EPOCH (a new
                     tail's RECONNECT may overtake it). IS_TAIL, no SUCC;
                     a MEMBER: ACKED = OPN, ACK(OPN) to PRED.
  NEWPRED(p, e)      master, after a middle server's failure, to its
                     successor. PRED = p, link not set up;
                     RECONNECT(OPN, e) to p.
  RECONNECT(n, e)    from the new successor; only if e is newer than
                     SUCC_EPOCH. SUCC = sender, no tail; one burst to it:
                     CATCHUP(max(OPN, TARGET)), then FWD(k, HIST[k]) for k
                     in (n, OPN]: the paper's resend of ``Sent``, and the
                     copy of Hist to a new tail. A new tail that has not
                     heard its own CATCHUP yet (or its JOIN) has no target
                     to hand on: it notes SUCC and answers at its CATCHUP.
  CATCHUP(m)         from PRED: the link is set up (what PRED sent before
                     it, to an earlier life of this server or before it
                     heard of this link, was dropped). TARGET = m;
                     CATCHING_UP and OPN >= m: MEMBER (a tail acks).
  JOIN(p, e)         master, to a server that is OUT: CATCHING_UP, PRED =
                     p, RECONNECT(0, e) to p; a tail with SUCC_EPOCH = e
                     unless a later joiner's RECONNECT came first.

A first spawn is a MEMBER at its place in 0 -> 1 -> ... -> t - 1. A
server that a HardKill stopped comes back with SPAWNS and the ghost counts
only: the first message it handles finds AWAKE = 0 and SPAWNS > 1, and it
is OUT before the message is looked at (no BOOT timer, and no message
dropped to tell the two apart; a mark the server sets itself would miss
the one killed before it handled anything, which is why the runtime
counts). An OUT server drops all but JOIN and a RECONNECT's note. The
invariant counts a server once AWAKE = 1. Queries are left out: they read
the tail's Hist and change nothing.

Safety invariant, after every delivery, over servers that are up, AWAKE
and MEMBER (the paper's two, sec. 3):
  code 1 -- Update Propagation: two of them hold different values at an
            index both have.
  code 2 -- one holds fewer entries than another knows acknowledged.

Seeded bug ``bug="no_resend"``: a server keeps ``Sent`` in a bounded
window, and when the successor a middle failure gives it lacks
max(1, 3L/16) entries or more (12 at L = 64, 1 at L = 8: more than the
window holds) its RECONNECT sends CATCHUP and nothing else: the updates
the dead server had not passed on are never resent, the next FWD lands at
n > OPN + 1, code 1. (With no bound at all, as ISSUE 40 first wrote it,
every lane of the benchmark's mix violates; the copy of Hist to a new
tail is another path and is right.)

Departures from the paper and what it leaves open are in
``benchmarks/configs/chain7-fifo.json``.

**The read-path fixture** (``make_chain_app(n)``, no ``log_cap``; the
differential tests' ``chain`` case). Actors form a chain head=0 -> ... ->
tail=n-1: external WRITEs enter at the head and replicate down the chain;
a version is COMMITTED when it reaches the tail, which sends an ACK back
up; each node's committed watermark only ever advances via tail-originated
ACKs. External READs may hit any node and are served from the committed
watermark. Invariant (code 1, phantom read): no alive node may ever have
SERVED a version newer than the tail's committed version. Seeded bug
``bug="read_uncommitted"``: reads are served from the latest *received*
version instead of the committed watermark; it needs a mid-chain Kill and
a read racing the replication.
"""

from __future__ import annotations

import random as _random
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..dsl import DSLApp, vset
from ..external_events import OP_START, Send, constant_message
from .common import DSLSendGenerator

T_WRITE = 1  # (tag, value, 0) external -> head
T_REPL = 2  # (tag, version, value) node i -> i+1
T_ACK = 3  # (tag, version, 0) node i -> i-1 (originates at tail)
T_READ = 4  # (tag, 0, 0) external -> any node

MSG_W = 3

VERSION = 0  # latest version received
VALUE = 1
COMMITTED = 2  # committed watermark (tail-originated)
SERVED = 3  # newest version this node ever served to a read


def make_chain_app(
    num_actors: int, bug: Optional[str] = None, name: str = "c",
    log_cap: Optional[int] = None,
) -> DSLApp:
    """Chain replication with its repairs where ``log_cap`` is given (the
    module doc); without it the read-path fixture."""
    if log_cap is not None:
        return _make_replication_app(num_actors, log_cap, bug, name)
    return _make_read_fixture(num_actors, bug, name)


def _make_read_fixture(num_actors: int, bug: Optional[str], name: str) -> DSLApp:
    n = num_actors
    assert n >= 2, "chain needs at least head and tail"
    state_width = 4
    max_outbox = 1

    def init_state(actor_id: int) -> np.ndarray:
        return np.zeros(state_width, np.int32)

    def _one(dst, tag, a, b):
        row = jnp.stack(
            [jnp.int32(1), dst.astype(jnp.int32), tag.astype(jnp.int32),
             a.astype(jnp.int32), b.astype(jnp.int32)]
        )
        return row[None, :]

    def _none():
        return jnp.zeros((max_outbox, 2 + MSG_W), jnp.int32)

    def on_write(actor_id, state, snd, msg):
        value = msg[1]
        is_head = actor_id == 0
        version = state[VERSION] + 1
        state = vset(state, VERSION, version, is_head)
        state = vset(state, VALUE, value, is_head)
        # Single-node chain commits immediately; else replicate to node 1.
        if n == 1:  # pragma: no cover - guarded by assert n >= 2
            return state, _none()
        tail_here = is_head & (n == 1)
        out = jnp.where(
            is_head,
            _one(jnp.int32(1), jnp.int32(T_REPL), version, value),
            _none(),
        )
        return state, out

    def on_repl(actor_id, state, snd, msg):
        version, value = msg[1], msg[2]
        in_chain = actor_id != 0
        newer = version > state[VERSION]
        apply_ = in_chain & newer
        state = vset(state, VERSION, version, apply_)
        state = vset(state, VALUE, value, apply_)
        is_tail = actor_id == n - 1
        # Tail: commit + ack upstream. Middle: forward down the chain.
        state = vset(
            state, COMMITTED,
            jnp.maximum(state[COMMITTED], version), apply_ & is_tail,
        )
        nxt = jnp.clip(actor_id + 1, 0, n - 1)
        prv = jnp.clip(actor_id - 1, 0, n - 1)
        out = jnp.where(
            apply_,
            jnp.where(
                is_tail,
                _one(jnp.asarray(prv), jnp.int32(T_ACK), version, jnp.int32(0)),
                _one(jnp.asarray(nxt), jnp.int32(T_REPL), version, value),
            ),
            _none(),
        )
        return state, out

    def on_ack(actor_id, state, snd, msg):
        version = msg[1]
        state = vset(
            state, COMMITTED, jnp.maximum(state[COMMITTED], version)
        )
        prv = jnp.clip(actor_id - 1, 0, n - 1)
        out = jnp.where(
            actor_id > 0,
            _one(jnp.asarray(prv), jnp.int32(T_ACK), version, jnp.int32(0)),
            _none(),
        )
        return state, out

    def on_read(actor_id, state, snd, msg):
        if bug == "read_uncommitted":
            # BUG: serve the latest received version — observable before
            # it commits, lost if the chain dies mid-replication.
            served = jnp.maximum(state[SERVED], state[VERSION])
        else:
            served = jnp.maximum(state[SERVED], state[COMMITTED])
        state = vset(state, SERVED, served)
        return state, _none()

    def handler(actor_id, state, snd, msg):
        tag = jnp.clip(msg[0], 1, 4) - 1
        return jax.lax.switch(
            tag, [on_write, on_repl, on_ack, on_read],
            actor_id, state, snd, msg,
        )

    def invariant(states, alive):
        """Phantom read: an alive node served a version beyond the alive
        tail's committed watermark."""
        committed_tail = states[n - 1, COMMITTED]
        served = states[:, SERVED]
        bad = jnp.any(alive & (served > committed_tail)) & alive[n - 1]
        return jnp.where(bad, jnp.int32(1), jnp.int32(0))

    return DSLApp(
        name=name,
        num_actors=n,
        state_width=state_width,
        msg_width=MSG_W,
        max_outbox=max_outbox,
        init_state=init_state,
        handler=handler,
        invariant=invariant,
        tag_names=("", "Write", "Repl", "Ack", "Read"),
    )


def chain_send_generator(app: DSLApp):
    """The master and the client of the replication protocol; for the
    read-path fixture, writes (to whoever: non-heads ignore) interleaved
    with reads."""
    if app.tag_names[1:2] == ("Update",):
        return ChainMaster(app)

    def make_msg(rng: _random.Random, counter: int):
        if counter > 8:
            return None
        if rng.random() < 0.5:
            return (T_WRITE, 10 + counter, 0)
        return (T_READ, 0, 0)

    return DSLSendGenerator(app, make_msg)


# ---------------------------------------------------------------------------
# Chain replication with its failure repairs (the module doc)
# ---------------------------------------------------------------------------

U_UPDATE = 1  # (tag, value, 0) client -> head
U_FWD = 2  # (tag, n, value) PRED -> SUCC
U_ACK = 3  # (tag, n, 0) SUCC -> PRED
U_BECOME_HEAD = 4  # (tag, epoch, 0) master
U_BECOME_TAIL = 5  # (tag, epoch, 0) master
U_NEWPRED = 6  # (tag, p, epoch) master
U_RECONNECT = 7  # (tag, n, epoch) new SUCC -> PRED
U_CATCHUP = 8  # (tag, m, 0) PRED -> SUCC, first on a link
U_JOIN = 9  # (tag, p, epoch) master
NUM_KINDS = 9
REPLICATION_BUGS = (None, "no_resend")

OUT, CATCHING_UP, MEMBER = 0, 1, 2
NONE = -1  # no PRED / no SUCC

STATUS = 0
IS_HEAD = 1
IS_TAIL = 2
PRED = 3
SUCC = 4
OPN = 5  # entries in Hist
ACKED = 6  # highest acknowledged: Sent is Hist's suffix (ACKED, OPN]
TARGET = 7  # entries a CATCHING_UP server needs to be a MEMBER
SPAWNS = 8  # DSLApp.spawn_count: 1 in a first life, more in a later one
AWAKE = 9  # it has handled one in this life
RESENT_ROWS = 10  # DURABLE ghost: FWD rows sent in RECONNECT bursts
RECONFIGS = 11  # DURABLE ghost: configuration messages applied
LINKED = 12  # PRED's CATCHUP has arrived: FWDs from it count
SUCC_EPOCH = 13  # master's epoch of what last set SUCC / IS_TAIL
OWES = 14  # SUCC reconnected before this server knew its own TARGET
HIST = 15  # HIST[L]


def _make_replication_app(
    num_actors: int, log_cap: int, bug: Optional[str], name: str
) -> DSLApp:
    n, L = num_actors, log_cap
    assert n >= 1 and L >= 1
    if bug not in REPLICATION_BUGS:
        raise ValueError(
            f"unknown chain bug {bug!r} (choices: {REPLICATION_BUGS[1:]})"
        )
    S = HIST + L
    # One lone message (row 0), then a RECONNECT's burst: CATCHUP and up
    # to L FWDs, in the order the FIFO link must keep.
    max_outbox = L + 2
    external = n  # the sender id of the client and of the master
    resend_window = max(1, 3 * L // 16)  # bug="no_resend": 12 at L = 64

    def init_state(actor_id: int) -> np.ndarray:
        st = np.zeros(S, np.int32)
        st[STATUS] = MEMBER
        st[IS_HEAD] = actor_id == 0
        st[IS_TAIL] = actor_id == n - 1
        st[PRED] = actor_id - 1  # NONE at the head
        st[SUCC] = actor_id + 1 if actor_id < n - 1 else NONE
        st[LINKED] = 1
        return st

    # What a respawn is before it looks at its first message: OUT, and
    # nothing else (its history is init_state's zeros already).
    out_scalars = np.zeros(HIST, np.int32)
    out_scalars[[PRED, SUCC]] = NONE
    volatile = np.ones(HIST, bool)
    volatile[[SPAWNS, RESENT_ROWS, RECONFIGS]] = False

    def one(dst, tag, a, b, valid):
        """A lone message ``(valid, dst, tag, a, b)``; none to nobody."""
        dst = jnp.asarray(dst, jnp.int32)
        return jnp.stack([
            (jnp.asarray(valid) & (dst >= 0)).astype(jnp.int32),
            jnp.maximum(dst, 0), jnp.int32(tag),
            jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32),
        ])

    nothing = jnp.zeros(5, jnp.int32)

    def put(st, ok, words):
        """``st[key] = val`` for each of ``words`` where ``ok``."""
        for key, val in words.items():
            st = vset(st, key, val, ok)
        return st

    def acks_as_tail(st, ok):
        """A MEMBER tail: all it holds is acknowledged, and it says so."""
        st = put(st, ok, {ACKED: st[OPN]})
        return st, one(st[PRED], U_ACK, st[OPN], 0, ok & (st[IS_HEAD] == 0))

    def on_update(me, st, snd, a, b):
        value = a
        hist = st[HIST:]
        ok = (
            (snd == external) & (st[STATUS] == MEMBER) & (st[IS_HEAD] == 1)
            & (st[OPN] < L) & ~jnp.any(hist == value)
        )
        st = jnp.concatenate([st[:HIST], vset(hist, st[OPN], value, ok)])
        opn = st[OPN] + 1
        tail = st[IS_TAIL] == 1
        st = put(st, ok, {OPN: opn})
        st = put(st, ok & tail, {ACKED: opn})
        return st, one(st[SUCC], U_FWD, opn, value, ok & ~tail), nothing

    def on_fwd(me, st, snd, a, b):
        nth, value = a, b
        ok = (
            (st[STATUS] != OUT) & (snd == st[PRED]) & (st[LINKED] == 1)
            & (nth > st[OPN])
        )
        hist = vset(st[HIST:], jnp.clip(nth, 1, L) - 1, value, ok)
        st = jnp.concatenate([st[:HIST], hist])
        st = put(st, ok, {OPN: jnp.minimum(nth, L)})
        caught_up = ok & (st[STATUS] == CATCHING_UP) & (st[OPN] >= st[TARGET])
        st = put(st, caught_up, {STATUS: MEMBER})
        tail = st[IS_TAIL] == 1
        st, ack = acks_as_tail(st, ok & tail & (st[STATUS] == MEMBER))
        fwd = one(st[SUCC], U_FWD, nth, value, ok & ~tail)
        return st, jnp.where(tail, ack, fwd), nothing

    def on_ack(me, st, snd, a, b):
        ok = (st[STATUS] != OUT) & (snd == st[SUCC])
        st = put(st, ok, {
            ACKED: jnp.maximum(st[ACKED], jnp.minimum(a, st[OPN]))
        })
        return st, one(st[PRED], U_ACK, a, 0, ok & (st[IS_HEAD] == 0)), nothing

    def on_become_head(me, st, snd, a, b):
        ok = (snd == external) & (st[STATUS] != OUT)
        st = put(st, ok, {
            IS_HEAD: 1, PRED: NONE, LINKED: 1,
            RECONFIGS: st[RECONFIGS] + 1,
        })
        return st, nothing, nothing

    def on_become_tail(me, st, snd, a, b):
        ok = (snd == external) & (st[STATUS] != OUT) & (a > st[SUCC_EPOCH])
        st = put(st, ok, {
            IS_TAIL: 1, SUCC: NONE, SUCC_EPOCH: a, OWES: 0,
            RECONFIGS: st[RECONFIGS] + 1,
        })
        st, ack = acks_as_tail(st, ok & (st[STATUS] == MEMBER))
        return st, ack, nothing

    def on_newpred(me, st, snd, a, b):
        ok = (snd == external) & (st[STATUS] != OUT)
        st = put(st, ok, {
            PRED: a, IS_HEAD: 0, LINKED: 0,
            RECONFIGS: st[RECONFIGS] + 1,
        })
        return st, one(a, U_RECONNECT, st[OPN], b, ok), nothing

    def burst_to_succ(st, ok, have):
        """CATCHUP(max(OPN, TARGET)) (a server that is itself catching up
        hands its own target on) and FWD(k, HIST[k]) for k in (have, OPN],
        to SUCC: ``(valid, dst, lo, hi, m)`` for the handler's outbox."""
        have = jnp.clip(have, 0, st[OPN])
        resent = st[OPN] - have
        if bug == "no_resend":
            # BUG: ``Sent`` is a bounded window; a successor that lacks
            # more than it holds gets CATCHUP and nothing else. (The copy
            # of Hist to a new tail, have = 0, is another path, and right.)
            resent = jnp.where(
                (have > 0) & (resent >= resend_window), 0, resent
            )
        st = put(st, ok, {RESENT_ROWS: st[RESENT_ROWS] + resent})
        return st, jnp.stack([
            ok.astype(jnp.int32), jnp.maximum(st[SUCC], 0), have,
            have + resent, jnp.maximum(st[OPN], st[TARGET]),
        ])

    def on_reconnect(me, st, snd, a, b):
        # An OUT server takes note too: its JOIN (an older epoch) may
        # still be on its way.
        ok = (snd != external) & (b > st[SUCC_EPOCH])
        st = put(st, ok, {SUCC: snd, IS_TAIL: 0, SUCC_EPOCH: b})
        # A new tail that has not heard its own CATCHUP has no target to
        # hand on: it answers when it has (on_catchup).
        knows = (st[STATUS] == MEMBER) | (st[TARGET] > 0)
        st = put(st, ok & ~knows, {OWES: 1})
        st, burst = burst_to_succ(st, ok & knows, a)
        return st, nothing, burst

    def on_catchup(me, st, snd, a, b):
        ok = (st[STATUS] != OUT) & (snd == st[PRED])
        st = put(st, ok, {
            LINKED: 1, TARGET: jnp.maximum(st[TARGET], a),
        })
        caught_up = ok & (st[STATUS] == CATCHING_UP) & (st[OPN] >= st[TARGET])
        st = put(st, caught_up, {STATUS: MEMBER})
        st, ack = acks_as_tail(st, caught_up & (st[IS_TAIL] == 1))
        st, burst = burst_to_succ(st, ok & (st[OWES] == 1), 0)
        st = put(st, ok, {OWES: 0})
        return st, ack, burst

    def on_join(me, st, snd, a, b):
        ok = (snd == external) & (st[STATUS] == OUT)
        # A later joiner's RECONNECT (a newer epoch) may have come first.
        tail = ok & (b > st[SUCC_EPOCH])
        st = put(st, ok, {
            STATUS: CATCHING_UP, PRED: a, RECONFIGS: st[RECONFIGS] + 1,
        })
        st = put(st, tail, {IS_TAIL: 1, SUCC: NONE, SUCC_EPOCH: b, OWES: 0})
        return st, one(a, U_RECONNECT, 0, b, ok), nothing

    branches = [
        on_update, on_fwd, on_ack, on_become_head, on_become_tail,
        on_newpred, on_reconnect, on_catchup, on_join,
    ]
    ks = jnp.arange(1, L + 1, dtype=jnp.int32)

    def handler(actor_id, state, snd, msg):
        # A respawn is OUT before it looks at its first message.
        respawn = (state[AWAKE] == 0) & (state[SPAWNS] > 1)
        scalars = jnp.where(
            respawn & volatile, jnp.asarray(out_scalars), state[:HIST]
        )
        state = jnp.concatenate([scalars, state[HIST:]])
        state = jnp.where(np.arange(S) == AWAKE, 1, state)
        tag = jnp.clip(msg[0], 1, NUM_KINDS) - 1
        state, lone, burst = jax.lax.switch(
            tag, branches, actor_id, state, snd, msg[1], msg[2]
        )
        # The outbox, built once: the lone message, then the burst
        # (valid, dst, lo, hi, m): CATCHUP(m), FWD(k, HIST[k]) for k in
        # (lo, hi], in that order.
        b_valid, b_dst, lo, hi, m = burst
        fwd_valid = (b_valid != 0) & (ks > lo) & (ks <= hi)
        fwds = jnp.stack([
            fwd_valid.astype(jnp.int32), jnp.full(L, 0, jnp.int32) + b_dst,
            jnp.full(L, U_FWD, jnp.int32), ks, state[HIST:],
        ], axis=1)
        catchup = jnp.stack([b_valid, b_dst, jnp.int32(U_CATCHUP), m, 0])
        return state, jnp.concatenate([lone[None], catchup[None], fwds])

    words, ids, int32 = np.arange(S), np.arange(n), np.iinfo(np.int32)
    # Word HIST + k is entry k of Hist; a scalar word is nobody's entry.
    entry = np.where(words >= HIST, words - HIST, int32.max).astype(np.int32)

    def column(states, word):
        """``states[:, word]`` as a select-and-sum over the whole rows."""
        return jnp.sum(jnp.where(words == word, states, 0), axis=1)

    def invariant(states, alive):
        # Every read is of whole [N, S] rows under a constant word mask: no
        # slice of Hist and no column ``states[:, word]`` either. A column
        # read makes the v5e compiler carry the step kernel's batched rows
        # with the N servers on its 128 lanes, and every pass of the step
        # over the rows then moves 18 times their bytes (DESIGN.md sec. 3).
        member = (
            alive & (column(states, AWAKE) == 1)
            & (column(states, STATUS) == MEMBER)
        )
        opn, acked = column(states, OPN), column(states, ACKED)
        # Update Propagation: the members agree pair by pair on the prefix
        # both hold exactly when each agrees, on its own OPN entries, with
        # the member that holds the most (the first of a tie), so its row
        # is the one reference: a one-hot select-and-sum, no gather.
        held = jnp.where(member, opn, -1)
        first = jnp.min(jnp.where(held == jnp.max(held), ids, n))
        longest = jnp.sum(
            jnp.where((ids == first)[:, None], states, 0), axis=0
        )
        own = member[:, None] & (entry[None, :] < opn[:, None])
        diverged = jnp.any(own & (states != longest[None, :]))
        # Some member lacks an update that some member has acknowledged.
        lost = (
            jnp.min(jnp.where(member, opn, int32.max))
            < jnp.max(jnp.where(member, acked, int32.min))
        )
        return jnp.where(
            diverged, jnp.int32(1), jnp.where(lost, jnp.int32(2), 0)
        )

    progress = (
        ("committed", lambda s: jnp.max(s[:, ACKED])),
        ("resent", lambda s: jnp.sum(s[:, RESENT_ROWS])),
        ("reconfigs", lambda s: jnp.sum(s[:, RECONFIGS])),
    )

    return DSLApp(
        name=name,
        num_actors=n,
        state_width=S,
        msg_width=MSG_W,
        max_outbox=max_outbox,
        init_state=init_state,
        handler=handler,
        invariant=invariant,
        tag_names=(
            "", "Update", "Fwd", "Ack", "BecomeHead", "BecomeTail",
            "NewPred", "Reconnect", "CatchUp", "Join",
        ),
        durable=(RESENT_ROWS, RECONFIGS),
        spawn_count=SPAWNS,
        progress=progress,
        channels="fifo",
    )


class ChainMaster:
    """The master and the one client of the replication protocol, as the
    fuzzer's send generator (the module doc). It keeps the chain as a
    list of names and what it has told each server; ``note_fault`` takes
    a dead server off the list at once and queues one that is up again.
    A draw is then the first of: BECOME_HEAD to a head that has not been
    told; NEWPRED to the first server whose predecessor is not the one it
    was told; BECOME_TAIL to a tail that has not been told; JOIN(the
    list's last) to the longest-waiting server that is up again, which is
    appended; else UPDATE(k) to the head, k = 1, 2, .... Deaths that fall
    between two draws are thus repaired against the list with all of the
    dead gone, and no message is addressed to a dead server. It draws
    nothing from ``rng``: the program's faults and waits are the fuzz."""

    def __init__(self, app: DSLApp):
        self.app = app
        self.reset()

    def reset(self) -> None:
        names = list(self.app.actor_names())
        self.chain = names
        self.waiting: list = []
        self.told_head = names[0]
        self.told_tail = names[-1]
        self.told_pred = dict(zip(names[1:], names))
        self.epoch = 0
        self.updates = 0

    def note_fault(self, op: int, name: str) -> None:
        if op == OP_START:
            if name not in self.chain and name not in self.waiting:
                self.waiting.append(name)
            return
        if name in self.chain:
            self.chain.remove(name)
        elif name in self.waiting:
            self.waiting.remove(name)
        self.told_pred.pop(name, None)

    def _configure(self):
        """The next configuration message ``(name, tag, a)``, or None."""
        chain = self.chain
        if self.told_head != chain[0]:
            self.told_head = chain[0]
            self.told_pred.pop(chain[0], None)
            return chain[0], U_BECOME_HEAD, None
        for pred, name in zip(chain, chain[1:]):
            if self.told_pred.get(name) != pred:
                self.told_pred[name] = pred
                return name, U_NEWPRED, pred
        if self.told_tail != chain[-1]:
            self.told_tail = chain[-1]
            return chain[-1], U_BECOME_TAIL, None
        if self.waiting:
            name = self.waiting.pop(0)
            pred = chain[-1]
            chain.append(name)
            self.told_pred[name] = pred
            self.told_tail = name
            return name, U_JOIN, pred
        return None

    def generate_row(self, rng: _random.Random, alive):
        if not self.chain:
            return None  # every member is dead: outside the failure model
        step = self._configure()
        if step is None:
            self.updates += 1
            return self.chain[0], (U_UPDATE, self.updates, 0)
        name, tag, pred = step
        self.epoch += 1
        if pred is None:
            return name, (tag, self.epoch, 0)
        return name, (tag, self.app.actor_id(pred), self.epoch)

    def generate(self, rng: _random.Random, alive):
        row = self.generate_row(rng, alive)
        return None if row is None else Send(row[0], constant_message(row[1]))
