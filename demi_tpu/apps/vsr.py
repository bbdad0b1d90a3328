"""Viewstamped Replication Revisited in the dual host/device DSL.

Liskov & Cowling, *Viewstamped Replication Revisited* (MIT-CSAIL-TR-2012-021):
normal operation (sec. 4.1), view change (4.2), recovery without a disk
but for a nonce (4.3) and state transfer (5.2), for N replicas that
tolerate f = (N - 1) // 2 failures; the primary of view v is replica
v mod N. The protocol of the Paxos/ZAB class whose messages carry whole
logs (DOVIEWCHANGE, STARTVIEW, RECOVERYRESPONSE, NEWSTATE), whose roles are
asymmetric, and whose crash-recovery is designed for this harness's fault:
a replica that a HardKill stopped restarts with nothing but one word on
disk (``DSLApp.durable``) and talks its way back.

A log entry is one word, the request's value (one client; values 1, 2, ...
in a program's send order; 0 is "no entry"). A message is
``(tag, f1, f2, f3, f4, log[L])``: ``msg_width`` = 5 + ``log_cap``. The
kinds, their fields and what a receiver does (``i`` is the sender):

  BOOT (timer; once a spawn)       INCARN += 1. The first: NORMAL in view 0.
                                   A later one: NONCE = INCARN, RECOVERING,
                                   RECOVERY(NONCE) to all others.
  COMMIT_TIMER (timer)             NORMAL primary: COMMIT(v, k) to all others.
  VIEW_TIMER (timer)               NORMAL backup or VIEW_CHANGE: one tick of
                                   the timeout; at the third in a row with
                                   nothing heard from the view's primary
                                   (HEARD; a PREPARE or COMMIT of the view,
                                   an installed log, a view just started):
                                   start view v + 1. RECOVERING:
                                   RECOVERY(NONCE) again.
  REQUEST(value)                   NORMAL primary, value new, room in the
                                   log: append, PREPARE(v, n, k, value) to all
                                   others. NORMAL backup, from the client:
                                   forward to primary(v).
  PREPARE(v, n, k, m)              n = OPN + 1: append, PREPAREOK(v, n).
                                   n <= OPN: PREPAREOK(v, OPN) again.
                                   n > OPN + 1: GETSTATE(v, OPN).
  PREPAREOK(v, n)                  primary: ACK[i] = max(ACK[i], n); COMMIT
                                   rises to the (f+1)-th largest ACK.
  COMMIT(v, k)                     COMMIT = max(COMMIT, min(k, OPN)); k > OPN:
                                   GETSTATE(v, OPN).
  STARTVIEWCHANGE(v)               v > VIEW: start view v. Then, in view v
                                   and VIEW_CHANGE: count i; at f others,
                                   once: DOVIEWCHANGE(v, v', n, k, log) to
                                   primary(v).
  DOVIEWCHANGE(v, v', n, k, log)   v > VIEW: start view v. primary(v) keeps
                                   the log with the largest (v', n); at f + 1
                                   it installs it and sends STARTVIEW(v, n, k,
                                   log) to all others.
  STARTVIEW(v, n, k, log)          a newer view, or this one unfinished:
                                   install; n > k: PREPAREOK(v, n).
  RECOVERY(x)                      NORMAL: RECOVERYRESPONSE(v, x, n, k, log)
                                   from the primary, (v, x, -1, -1, zeros)
                                   from a backup.
  RECOVERYRESPONSE(v, x, n, k, l)  RECOVERING, x = NONCE: count i; keep the
                                   newest primary's; at f + 1 senders with
                                   the newest view's primary among them:
                                   install, NORMAL.
  GETSTATE(v, n')                  NORMAL in view v: NEWSTATE(v, n, k, log).
  NEWSTATE(v, n, k, log)           NORMAL, and newer: install.

*Start view v*: VIEW = v, VIEW_CHANGE, the SVC and DVC accumulators
cleared, STARTVIEWCHANGE(v) to all others. *The view rule* of PREPARE,
PREPAREOK and COMMIT: only a NORMAL replica acts; an older view's is
dropped; a newer view's cuts the log to COMMIT and asks the sender,
GETSTATE(v, COMMIT). A BOOTING replica (the init state) drops everything
but BOOT; a RECOVERING one acts on RECOVERYRESPONSE and its timers only.

Departures from the paper and what it leaves open are in
``benchmarks/configs/vsr5-recovery.json``; in short: no client table (a
REQUEST is deduplicated by its value), no checkpoints (5.1), no
reconfiguration (7); NEWSTATE carries the whole log; a backup forwards a
client's REQUEST (the client's resend to all) and a forwarded one is not
forwarded again; a recovering replica sends RECOVERY again on its view
timer; the view-change timeout is three timer ticks; the primary counts an
ack only up to what its own log holds.

Safety invariant, after every delivery, over replicas that are up and
NORMAL or VIEW_CHANGE:
  code 1 -- two of them hold different entries at an index both count
            committed.
  code 2 -- one counts more committed than its log holds: a committed
            operation fell out of its log.

Seeded bugs:
  bug="recover_any"  -- recovery ends at f + 1 responses whatever view the
                        held primary's response is of, and with none held
                        the replica comes back with an empty log (4.3.3's
                        condition dropped): one of the f + 1 holders of a
                        committed entry forgets it, and a later view change
                        can lose it.
  bug="dvc_by_opnum" -- the new primary takes the DOVIEWCHANGE log with the
                        largest n and ignores v': a long stale log from an
                        old view wins.
"""

from __future__ import annotations

import random as _random
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..dsl import DSLApp, row_set, seg_set, vget, vset
from .common import DSLSendGenerator

# Message tags.
T_BOOT = 1  # timer
T_COMMIT_TIMER = 2  # timer
T_VIEW_TIMER = 3  # timer
T_REQUEST = 4  # (tag, value)
T_PREPARE = 5  # (tag, v, n, k, m)
T_PREPAREOK = 6  # (tag, v, n)
T_COMMIT = 7  # (tag, v, k)
T_SVC = 8  # (tag, v)
T_DVC = 9  # (tag, v, v', n, k, log)
T_STARTVIEW = 10  # (tag, v, n, k, 0, log)
T_RECOVERY = 11  # (tag, x)
T_RECRESP = 12  # (tag, v, x, n, k, log)
T_GETSTATE = 13  # (tag, v, n')
T_NEWSTATE = 14  # (tag, v, n, k, 0, log)
NUM_TAGS = 14

# VIEW_TIMER ticks a backup waits for its primary before it starts a view
# change: the timeout of sec. 4.2.1 in timer deliveries (the paper gives no
# number; a deployment's is several heartbeat intervals).
PATIENCE = 3
MSG_HEAD = 5  # tag and four fields; the log follows
BUGS = (None, "recover_any", "dvc_by_opnum")

# Status.
BOOTING, NORMAL, VIEW_CHANGE, RECOVERING = 0, 1, 2, 3

# State layout: the scalars, then LOG[L], ACK[N], DVC_LOG[L], REC_LOG[L].
VIEW = 0
STATUS = 1
OPN = 2  # entries in the log
COMMIT = 3  # entries committed
LAST_NORMAL = 4  # v': the last view this replica was NORMAL in
INCARN = 5  # DURABLE: spawns so far; the nonce's counter (4.3)
NONCE = 6
SVC_MASK = 7  # senders of STARTVIEWCHANGE(VIEW)
DVC_SENT = 8
DVC_MASK = 9  # senders of DOVIEWCHANGE(VIEW), at primary(VIEW)
DVC_BEST_V = 10
DVC_BEST_N = 11
DVC_MAX_K = 12
REC_MASK = 13  # senders of RECOVERYRESPONSE(NONCE)
REC_VIEW = 14  # the largest view any of them was in
REC_PRIM_VIEW = 15  # the view of the primary's response held (-1: none)
REC_N = 16
REC_K = 17
LOG_ROWS_SENT = 18  # DURABLE ghost: rows sent that carry a log
HEARD = 19  # VIEW_TIMER ticks left before this replica suspects its primary
LOG = 20


def state_width(n: int, log_cap: int) -> int:
    return LOG + n + 3 * log_cap


def make_vsr_app(
    num_actors: int,
    log_cap: int = 8,
    bug: Optional[str] = None,
    name: str = "v",
) -> DSLApp:
    n = num_actors
    L = log_cap
    if not 3 <= n <= 30:
        raise ValueError("vsr needs 3..30 replicas (f >= 1; masks are int32)")
    if bug not in BUGS:
        raise ValueError(f"unknown vsr bug {bug!r} (choices: {BUGS[1:]})")
    f = (n - 1) // 2
    S = state_width(n, L)
    W = MSG_HEAD + L
    ACK = LOG + L
    DVC_LOG = ACK + n
    REC_LOG = DVC_LOG + L
    ids = jnp.arange(n, dtype=jnp.int32)
    no_log = jnp.zeros(L, jnp.int32)

    def init_state(actor_id: int) -> np.ndarray:
        s = np.zeros(S, np.int32)  # BOOTING
        s[REC_PRIM_VIEW] = -1
        return s

    def initial_msgs(actor_id: int) -> np.ndarray:
        rows = np.zeros((3, 2 + W), np.int32)
        rows[:, 0] = 1
        rows[:, 1] = actor_id
        rows[:, 2] = (T_BOOT, T_COMMIT_TIMER, T_VIEW_TIMER)
        return rows

    # -- helpers (all traced) ---------------------------------------------
    def primary(v):
        return v % n

    def popcount(mask):
        return jnp.sum((mask >> ids) & 1)

    def is_normal(state):
        return state[STATUS] == NORMAL

    def empty_outbox():
        return jnp.zeros((n, 2 + W), jnp.int32)

    def put(outbox, slot, valid, dst, tag, f1=0, f2=0, f3=0, f4=0, log=no_log):
        """One row in ``slot`` of ``outbox``, where ``valid``."""
        head = jnp.stack([
            jnp.asarray(x, jnp.int32)
            for x in (valid, dst, tag, f1, f2, f3, f4)
        ])
        return row_set(outbox, slot, jnp.concatenate([head, log]), valid)

    def to_others(actor_id, enabled, tag, f1=0, f2=0, f3=0, f4=0, log=no_log):
        """Row i goes to replica i, for every i but the sender (whose own
        slot stays free for one more row)."""
        valid = ((ids != actor_id) & enabled).astype(jnp.int32)
        zeros = jnp.zeros(n, jnp.int32)
        head = jnp.stack(
            [valid, ids, zeros + tag, zeros + f1, zeros + f2, zeros + f3,
             zeros + f4],
            axis=1,
        )
        return jnp.concatenate(
            [head, jnp.broadcast_to(log[None, :], (n, L))], axis=1
        )

    def log_of(state):
        return state[LOG : LOG + L]

    def sent_logs(state, rows, enabled):
        """The ghost count of rows sent that carry a log."""
        return vset(
            state, LOG_ROWS_SENT, state[LOG_ROWS_SENT] + rows, enabled
        )

    def install(state, enabled, view, opn, commit, log):
        """NORMAL in ``view`` with ``log``: what STARTVIEW, NEWSTATE, the
        end of a recovery and the new primary's own step share."""
        new = seg_set(state, LOG, log)
        new = vset(new, VIEW, view)
        new = vset(new, STATUS, NORMAL)
        new = vset(new, LAST_NORMAL, view)
        new = vset(new, OPN, opn)
        new = vset(new, COMMIT, commit)
        new = vset(new, HEARD, PATIENCE)
        return jnp.where(enabled, new, state)

    def as_primary(actor_id, state, enabled):
        """ACK = 0 but ACK[self] = OPN: nobody has acknowledged anything
        in this view yet."""
        acks = vset(jnp.zeros(n, jnp.int32), actor_id, state[OPN])
        return jnp.where(enabled, seg_set(state, ACK, acks), state)

    def start_view(actor_id, state, v, enabled):
        """VIEW = v, VIEW_CHANGE, accumulators cleared, SVC(v) to all
        others (sec. 4.2.1)."""
        new = vset(state, VIEW, v)
        new = vset(new, STATUS, VIEW_CHANGE)
        new = vset(new, HEARD, PATIENCE)  # a whole timeout before the next view
        for field in (SVC_MASK, DVC_SENT, DVC_MASK, DVC_BEST_V, DVC_BEST_N,
                      DVC_MAX_K):
            new = vset(new, field, 0)
        new = seg_set(new, DVC_LOG, no_log)
        return (
            jnp.where(enabled, new, state),
            to_others(actor_id, enabled, T_SVC, v),
        )

    def take_dvc(state, enabled, sender, v_last, opn, commit, log):
        """One DOVIEWCHANGE into the new primary's accumulator: keep the
        log with the largest (v', n) (sec. 4.2.3)."""
        first = state[DVC_MASK] == 0
        if bug == "dvc_by_opnum":
            # BUG: v' ignored; the longest log wins whatever view it is of.
            better = opn > state[DVC_BEST_N]
        else:
            better = (v_last > state[DVC_BEST_V]) | (
                (v_last == state[DVC_BEST_V]) & (opn > state[DVC_BEST_N])
            )
        keep = first | better
        new = vset(state, DVC_MASK, state[DVC_MASK] | (jnp.int32(1) << sender))
        new = vset(new, DVC_MAX_K, jnp.maximum(state[DVC_MAX_K], commit))
        held = seg_set(new, DVC_LOG, log)
        held = vset(held, DVC_BEST_V, v_last)
        held = vset(held, DVC_BEST_N, opn)
        new = jnp.where(keep, held, new)
        return jnp.where(enabled, new, state)

    def finish_view_change(actor_id, state, enabled):
        """At f + 1 DOVIEWCHANGEs the new primary installs the best log
        and starts the view (sec. 4.2.3-4)."""
        done = enabled & (popcount(state[DVC_MASK]) >= f + 1)
        new = install(
            state, done, state[VIEW], state[DVC_BEST_N],
            jnp.maximum(state[COMMIT], state[DVC_MAX_K]),
            state[DVC_LOG : DVC_LOG + L],
        )
        new = as_primary(actor_id, new, done)
        new = sent_logs(new, n - 1, done)
        out = to_others(
            actor_id, done, T_STARTVIEW, new[VIEW], new[OPN], new[COMMIT],
            log=log_of(new),
        )
        return new, out, done

    def view_rule(state, snd, v):
        """PREPARE, PREPAREOK and COMMIT: ``act`` in this view; a newer
        view's cuts the log to COMMIT and asks the sender for its state
        (sec. 5.2)."""
        normal = is_normal(state)
        act = normal & (v == state[VIEW])
        behind = normal & (v > state[VIEW])
        kept = jnp.minimum(state[OPN], state[COMMIT])
        cut = vset(state, OPN, kept)
        cut = seg_set(
            cut, LOG, jnp.where(jnp.arange(L) < kept, log_of(state), 0)
        )
        state = jnp.where(behind, cut, state)
        state = vset(state, HEARD, PATIENCE, act)
        out = put(
            empty_outbox(), 0, behind, jnp.clip(snd, 0, n - 1), T_GETSTATE,
            v, state[COMMIT],
        )
        return state, out, act

    # -- per-tag handlers --------------------------------------------------
    def on_boot(actor_id, state, snd, msg):
        booting = state[STATUS] == BOOTING
        incarn = state[INCARN] + 1
        first = booting & (incarn == 1)
        again = booting & (incarn > 1)
        state = vset(state, INCARN, incarn, booting)
        state = vset(state, STATUS, NORMAL, first)
        state = vset(state, HEARD, PATIENCE, first)
        state = vset(state, STATUS, RECOVERING, again)
        state = vset(state, NONCE, incarn, again)
        return state, to_others(actor_id, again, T_RECOVERY, incarn)

    def on_commit_timer(actor_id, state, snd, msg):
        lead = is_normal(state) & (primary(state[VIEW]) == actor_id)
        out = to_others(actor_id, lead, T_COMMIT, state[VIEW], state[COMMIT])
        return state, put(out, actor_id, True, actor_id, T_COMMIT_TIMER)

    def on_view_timer(actor_id, state, snd, msg):
        waiting = (
            is_normal(state) & (primary(state[VIEW]) != actor_id)
        ) | (state[STATUS] == VIEW_CHANGE)
        # The timeout of sec. 4.2.1 without a clock: each VIEW_TIMER is a
        # tick, hearing from the view's primary winds it up again.
        suspect = waiting & (state[HEARD] == 0)
        state = vset(state, HEARD, jnp.maximum(state[HEARD] - 1, 0), waiting)
        recovering = state[STATUS] == RECOVERING
        state, out = start_view(actor_id, state, state[VIEW] + 1, suspect)
        out = jnp.where(
            recovering,
            to_others(actor_id, recovering, T_RECOVERY, state[NONCE]),
            out,
        )
        return state, put(out, actor_id, True, actor_id, T_VIEW_TIMER)

    def on_request(actor_id, state, snd, msg):
        value = msg[1]
        lead = primary(state[VIEW])
        normal = is_normal(state)
        known = jnp.any((log_of(state) == value) & (jnp.arange(L) < state[OPN]))
        can = normal & (lead == actor_id) & ~known & (state[OPN] < L)
        slot = jnp.clip(state[OPN], 0, L - 1)
        state = vset(state, LOG + slot, value, can)
        state = vset(state, OPN, state[OPN] + 1, can)
        state = vset(state, ACK + actor_id, state[OPN], can)
        out = to_others(
            actor_id, can, T_PREPARE, state[VIEW], state[OPN], state[COMMIT],
            value,
        )
        # A backup forwards what the client sent it (the client's resend
        # to all); a forwarded request is not forwarded again.
        forward = normal & (lead != actor_id) & (snd >= n)
        return state, put(out, actor_id, forward, lead, T_REQUEST, value)

    def on_prepare(actor_id, state, snd, msg):
        v, opn, commit, value = msg[1], msg[2], msg[3], msg[4]
        state, out, act = view_rule(state, snd, v)
        nxt = act & (opn == state[OPN] + 1) & (state[OPN] < L)
        have = act & (opn <= state[OPN])
        gap = act & (opn > state[OPN] + 1)
        slot = jnp.clip(state[OPN], 0, L - 1)
        state = vset(state, LOG + slot, value, nxt)
        state = vset(state, OPN, opn, nxt)
        state = vset(
            state, COMMIT,
            jnp.maximum(state[COMMIT], jnp.minimum(commit, state[OPN])), nxt,
        )
        lead = primary(state[VIEW])
        out = put(out, 0, nxt | have, lead, T_PREPAREOK, v, state[OPN])
        out = put(out, 0, gap, lead, T_GETSTATE, v, state[OPN])
        return state, out

    def on_prepareok(actor_id, state, snd, msg):
        v, opn = msg[1], msg[2]
        state, out, act = view_rule(state, snd, v)
        count = act & (primary(state[VIEW]) == actor_id)
        acks = state[ACK : ACK + n]
        sender = jnp.clip(snd, 0, n - 1)
        acks = vset(acks, sender, jnp.maximum(vget(acks, sender), opn), count)
        state = seg_set(state, ACK, acks)
        # The (f+1)-th largest: the most entries that f + 1 replicas, this
        # one among them, have acknowledged; never more than it holds.
        reached = jnp.sum(acks[None, :] >= acks[:, None], axis=1) >= f + 1
        quorum = jnp.max(jnp.where(reached, acks, 0))
        state = vset(
            state, COMMIT,
            jnp.maximum(state[COMMIT], jnp.minimum(quorum, state[OPN])), count,
        )
        return state, out

    def on_commit(actor_id, state, snd, msg):
        v, commit = msg[1], msg[2]
        state, out, act = view_rule(state, snd, v)
        state = vset(
            state, COMMIT,
            jnp.maximum(state[COMMIT], jnp.minimum(commit, state[OPN])), act,
        )
        out = put(
            out, 0, act & (commit > state[OPN]), primary(state[VIEW]),
            T_GETSTATE, v, state[OPN],
        )
        return state, out

    def in_view_change(state):
        return is_normal(state) | (state[STATUS] == VIEW_CHANGE)

    def on_svc(actor_id, state, snd, msg):
        v = msg[1]
        state, out = start_view(
            actor_id, state, v, in_view_change(state) & (v > state[VIEW])
        )
        count = (state[STATUS] == VIEW_CHANGE) & (v == state[VIEW])
        sender = jnp.clip(snd, 0, n - 1)
        state = vset(
            state, SVC_MASK, state[SVC_MASK] | (jnp.int32(1) << sender), count
        )
        others = popcount(state[SVC_MASK] & ~(jnp.int32(1) << actor_id))
        send = count & (others >= f) & (state[DVC_SENT] == 0)
        state = vset(state, DVC_SENT, 1, send)
        lead = primary(state[VIEW])
        own = send & (lead == actor_id)
        away = send & (lead != actor_id)
        # Its own DOVIEWCHANGE enters the new primary's accumulator
        # without a message; everyone else's is a row with the log.
        state = take_dvc(
            state, own, actor_id, state[LAST_NORMAL], state[OPN],
            state[COMMIT], log_of(state),
        )
        state = sent_logs(state, 1, away)
        out = put(
            out, actor_id, away, lead, T_DVC, state[VIEW], state[LAST_NORMAL],
            state[OPN], state[COMMIT], log_of(state),
        )
        state, started, done = finish_view_change(actor_id, state, own)
        return state, jnp.where(done, started, out)

    def on_dvc(actor_id, state, snd, msg):
        v, v_last, opn, commit = msg[1], msg[2], msg[3], msg[4]
        state, out = start_view(
            actor_id, state, v, in_view_change(state) & (v > state[VIEW])
        )
        count = (
            (state[STATUS] == VIEW_CHANGE) & (v == state[VIEW])
            & (primary(v) == actor_id)
        )
        state = take_dvc(
            state, count, jnp.clip(snd, 0, n - 1), v_last, opn, commit,
            msg[MSG_HEAD:],
        )
        state, started, done = finish_view_change(actor_id, state, count)
        return state, jnp.where(done, started, out)

    def on_startview(actor_id, state, snd, msg):
        v, opn, commit = msg[1], msg[2], msg[3]
        take = in_view_change(state) & (
            (v > state[VIEW]) | ((v == state[VIEW]) & ~is_normal(state))
        )
        state = install(
            state, take, v, opn, jnp.maximum(state[COMMIT], commit),
            msg[MSG_HEAD:],
        )
        out = put(
            empty_outbox(), 0, take & (opn > commit), primary(v), T_PREPAREOK,
            v, opn,
        )
        return state, out

    def on_recovery(actor_id, state, snd, msg):
        normal = is_normal(state)
        lead = normal & (primary(state[VIEW]) == actor_id)
        state = sent_logs(state, 1, lead)
        out = put(
            empty_outbox(), 0, normal, jnp.clip(snd, 0, n - 1), T_RECRESP,
            state[VIEW], msg[1],
            jnp.where(lead, state[OPN], -1), jnp.where(lead, state[COMMIT], -1),
            jnp.where(lead, log_of(state), 0),
        )
        return state, out

    def on_recresp(actor_id, state, snd, msg):
        v, nonce, opn, commit = msg[1], msg[2], msg[3], msg[4]
        count = (state[STATUS] == RECOVERING) & (nonce == state[NONCE])
        sender = jnp.clip(snd, 0, n - 1)
        new = vset(state, REC_MASK, state[REC_MASK] | (jnp.int32(1) << sender))
        new = vset(new, REC_VIEW, jnp.maximum(state[REC_VIEW], v))
        held = seg_set(new, REC_LOG, msg[MSG_HEAD:])
        held = vset(held, REC_PRIM_VIEW, v)
        held = vset(held, REC_N, opn)
        held = vset(held, REC_K, commit)
        new = jnp.where((opn >= 0) & (v >= state[REC_PRIM_VIEW]), held, new)
        state = jnp.where(count, new, state)
        enough = count & (popcount(state[REC_MASK]) >= f + 1)
        have = state[REC_PRIM_VIEW] >= 0
        if bug == "recover_any":
            # BUG: sec. 4.3.3 waits for the primary of the NEWEST view among
            # the responses; this ends at f + 1 whoever they are from, with
            # an older primary's log or, with no primary heard, with none.
            done = enough
            state = install(
                state, done,
                jnp.where(have, state[REC_PRIM_VIEW], state[REC_VIEW]),
                jnp.where(have, state[REC_N], 0),
                jnp.where(have, state[REC_K], 0),
                jnp.where(have, state[REC_LOG : REC_LOG + L], 0),
            )
        else:
            done = enough & have & (state[REC_PRIM_VIEW] == state[REC_VIEW])
            state = install(
                state, done, state[REC_PRIM_VIEW], state[REC_N], state[REC_K],
                state[REC_LOG : REC_LOG + L],
            )
        state = as_primary(actor_id, state, done)
        return state, empty_outbox()

    def on_getstate(actor_id, state, snd, msg):
        give = is_normal(state) & (msg[1] == state[VIEW])
        state = sent_logs(state, 1, give)
        out = put(
            empty_outbox(), 0, give, jnp.clip(snd, 0, n - 1), T_NEWSTATE,
            state[VIEW], state[OPN], state[COMMIT], log=log_of(state),
        )
        return state, out

    def on_newstate(actor_id, state, snd, msg):
        v, opn, commit = msg[1], msg[2], msg[3]
        take = is_normal(state) & (
            (v > state[VIEW]) | ((v == state[VIEW]) & (opn > state[OPN]))
        )
        state = install(
            state, take, v, opn,
            jnp.maximum(state[COMMIT], jnp.minimum(commit, opn)),
            msg[MSG_HEAD:],
        )
        return state, empty_outbox()

    branches = [
        on_boot, on_commit_timer, on_view_timer, on_request, on_prepare,
        on_prepareok, on_commit, on_svc, on_dvc, on_startview, on_recovery,
        on_recresp, on_getstate, on_newstate,
    ]

    def handler(actor_id, state, snd, msg):
        # Every branch is gated on the status it needs, so a BOOTING
        # replica drops everything but BOOT (its timers re-arm and do
        # nothing) and a RECOVERING one acts on RECOVERYRESPONSE and its
        # timers only.
        tag = jnp.clip(msg[0], 1, NUM_TAGS) - 1
        return jax.lax.switch(tag, branches, actor_id, state, snd, msg)

    # -- invariant ---------------------------------------------------------
    def invariant(states, alive):
        status = states[:, STATUS]
        live = alive & ((status == NORMAL) | (status == VIEW_CHANGE))
        opn, commit = states[:, OPN], states[:, COMMIT]
        logs = states[:, LOG : LOG + L]
        both = live[:, None] & live[None, :]
        # An index both count committed, and both hold an entry at.
        upto = jnp.minimum(
            jnp.minimum(commit[:, None], commit[None, :]),
            jnp.minimum(opn[:, None], opn[None, :]),
        )
        shared = jnp.arange(L)[None, None, :] < upto[:, :, None]
        differ = logs[:, None, :] != logs[None, :, :]
        diverged = jnp.any(both[:, :, None] & shared & differ)
        lost = jnp.any(live & (commit > opn))
        return jnp.where(
            diverged, jnp.int32(1), jnp.where(lost, jnp.int32(2), 0)
        )

    # -- progress counts (DSLApp.progress) ---------------------------------
    def recovered(states):
        return jnp.sum(
            (states[:, INCARN] > 1) & (states[:, STATUS] == NORMAL)
        )

    progress = (
        ("views", lambda s: jnp.max(s[:, VIEW])),
        ("recoveries", lambda s: jnp.sum(jnp.maximum(s[:, INCARN] - 1, 0))),
        ("recovered", recovered),
        ("committed", lambda s: jnp.max(s[:, COMMIT])),
        ("log_rows", lambda s: jnp.sum(s[:, LOG_ROWS_SENT])),
    )

    return DSLApp(
        name=name,
        num_actors=n,
        state_width=S,
        msg_width=W,
        max_outbox=n,
        init_state=init_state,
        handler=handler,
        initial_msgs=initial_msgs,
        invariant=invariant,
        timer_tags=(T_BOOT, T_COMMIT_TIMER, T_VIEW_TIMER),
        tag_names=(
            "", "Boot", "CommitTimer", "ViewTimer", "Request", "Prepare",
            "PrepareOk", "Commit", "StartViewChange", "DoViewChange",
            "StartView", "Recovery", "RecoveryResponse", "GetState",
            "NewState",
        ),
        durable=(INCARN, LOG_ROWS_SENT),
        progress=progress,
    )


def vsr_send_generator(app: DSLApp) -> DSLSendGenerator:
    """The one client's requests: the k-th send of a program is
    REQUEST(k), to a replica drawn among those that are up."""

    def make_msg(rng: _random.Random, counter: int):
        return (T_REQUEST, counter) + (0,) * (app.msg_width - 2)

    return DSLSendGenerator(app, make_msg)
