"""Multi-Paxos as published, in the dual host/device DSL.

van Renesse & Altinbuken, *Paxos Made Moderately Complex* (PMMC), ACM
Computing Surveys 47(3), 2015: the pseudo-code figures for replica,
acceptor, scout, commander and leader, the per-slot state reduction of
its "Paxos made pragmatic" section, and its sizing rule for f failures:
f + 1 replicas, f + 1 leaders, 2f + 1 acceptors. ``--nodes`` is their sum;
actors 0..f are the replicas, the next f + 1 the leaders, the last 2f + 1
the acceptors (f = 2: 3 + 3 + 5 = 11).

The network is the paper's: a message between correct processes is
received at least once, in any order, and may be lost on its way
(``DSLApp.channels = "datagram"``: the scheduler re-delivers and loses
messages at its choice). It is the protocol that is wrong without
deduplication: scouts and commanders wait on a *set* of acceptors.

A ballot is (round, leader), ordered lexicographically, carried as one
int, ``round * leaders + leader index``; bottom is -1. A command is an
int 1, 2, ... (0: none); slots are 1 .. ``log_cap``. A message is
``(tag, f1, f2, f3, ballot[L], command[L])``: ``msg_width`` = 4 + 2 L,
the table being an acceptor's whole accepted state, which only P1B fills.

  REQUEST(c)        client -> replica: c joins ``requests``; ``propose()``.
  PROPOSE(s, c)     replica -> every leader: if no proposal holds s, record
                    it and, if active, a commander for (ballot_num, s, c).
  DECISION(s, c)    commander -> every replica: record it; while
                    ``slot_out`` is decided as c': a proposal c'' != c' of
                    ours for that slot goes back to ``requests``;
                    ``perform(c')`` (not counted, but ``slot_out += 1``,
                    if c' was performed in a lower slot); ``propose()``.
  P1A(b)            scout -> every acceptor: if b > ballot_num adopt it;
                    answer P1B(ballot_num, b, accepted[1..L]).
  P1B(b', b, r)     acceptor a -> the scout of ballot b: if b' == b, fold r
                    into pvalues (per slot the higher ballot wins: pmax
                    kept running), waitfor -= {a}, and once |waitfor| <
                    acceptors / 2: *adopted*: proposals := proposals <|
                    pmax(pvalues), a commander for every (s, c) in
                    proposals, active. Else *preempted(b')*.
  P2A(b, s, c)      commander -> every acceptor: if b == ballot_num (the
                    paper's test) accept; answer P2B(ballot_num, s, b).
  P2B(b', s, b)     acceptor a -> the commander of (b, s): if b' == b,
                    waitfor -= {a} and below acceptors / 2 DECISION(s, c)
                    to every replica, and the commander is done. Else
                    *preempted(b')*, and the commander is done if b' > b.
  BACKOFF(b)        (timer) a preempted leader starts its scout.

``propose()``: while ``slot_in < slot_out + WINDOW`` and ``requests`` is
not empty: if ``slot_in`` is undecided take the lowest command of
``requests``, record (slot_in, c) in ``proposals``, PROPOSE(slot_in, c)
to every leader; ``slot_in += 1`` either way. *Preempted(b')*: if b' >
ballot_num: active = false, ballot_num = (round(b') + 1, self), and a
BACKOFF timer.

Scouts and commanders are state of their leader, not processes: one
scout (its ``waitfor``, its running pmax) and one commander a slot (its
ballot and its ``waitfor``, in one word). A commander outlives its
leader's preemption, as the paper's processes do, until it decides, hears
a higher ballot, or the next adoption's commander for its slot replaces
it (as if the earlier one's replies were lost, which this network may
do). A reply therefore names what it answers (P1B the scout's ballot,
P2B the commander's ballot and slot) and is dropped where that scout or
commander no longer is, as a message to a process that exited is. The
other departures from the paper are listed in
``benchmarks/configs/paxos11-datagram.json``.

Safety invariant, after every delivery, over replicas that are up:
  code 1 -- two different commands are decided for one slot: two
            replicas hold them, or one replica was handed both.

Seeded bug:
  bug="count_replies" -- scout and commanders keep a count of matching
                         replies and fire at a majority of acceptors, not
                         a set of responders. Over a network that never
                         repeats a message every acceptor answers a given
                         P1A or P2A once and nothing changes; over one
                         that does, a commander decides a slot on one
                         acceptor's answer heard thrice while a higher
                         ballot decides another command there.
"""

from __future__ import annotations

import random as _random
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..dsl import DSLApp, seg_set, vget, vset
from ..external_events import Send, constant_message

# Message tags.
T_REQUEST = 1
T_PROPOSE = 2
T_DECISION = 3
T_P1A = 4
T_P1B = 5
T_P2A = 6
T_P2B = 7
T_BACKOFF = 8  # timer
NUM_TAGS = 8
MSG_HEAD = 4  # tag and three fields; the accepted table follows
BUGS = (None, "count_replies")

# Slots a replica may propose for ahead of the first it has not performed
# (the paper's WINDOW).
WINDOW = 5

# State layout: eight scalars (a role reads its own), then L-word tables.
BASE = 8
# Replica.
R_SLOT_IN = 0
R_SLOT_OUT = 1
R_PERFORMED = 2  # commands performed (a command decided twice counts once)
R_CONFLICT = 3  # handed a second, different decision for a slot it holds
# tables: REQUESTS (a flag a command), PROPOSALS, DECISIONS (a command a slot)
# Leader.
L_BALLOT = 0
L_ACTIVE = 1
L_SCOUTING = 2
L_SCOUT_WAIT = 3  # the acceptors that answered (a mask; the bug: a count)
L_ADOPTIONS = 4  # ghost
L_PREEMPTS = 5  # ghost
# tables: PROPOSALS, PMAX_BALLOT, PMAX_COMMAND, COMMANDER (per slot 0, or
# its ballot + 1 above the bits of the acceptors that answered)
# Acceptor.
A_BALLOT = 0
# tables: ACCEPTED_BALLOT, ACCEPTED_COMMAND (the highest ballot's, a slot)

BOTTOM = -1


def role_counts(num_actors: int):
    """(replicas, leaders, acceptors) of a 4f + 3-actor deployment."""
    f, rest = divmod(num_actors - 3, 4)
    if rest or f < 1:
        raise ValueError(
            f"paxos takes 2f + 1 + 2(f + 1) actors (f + 1 replicas, f + 1 "
            f"leaders, 2f + 1 acceptors: 7, 11, 15, ...), not {num_actors}"
        )
    return f + 1, f + 1, 2 * f + 1


def state_width(log_cap: int) -> int:
    """The widest role's row: the leader's four tables."""
    return BASE + 4 * log_cap


def make_paxos_app(
    num_actors: int = 11,
    log_cap: int = 8,
    bug: Optional[str] = None,
    name: str = "p",
) -> DSLApp:
    n = num_actors
    R, NL, NA = role_counts(n)
    L = log_cap
    if bug not in BUGS:
        raise ValueError(f"unknown paxos bug {bug!r} (choices: {BUGS[1:]})")
    if NA > 15 or L < 3:
        raise ValueError("paxos needs log_cap >= 3 and at most 15 acceptors")
    LEAD0, ACC0 = R, R + NL
    S = state_width(L)
    W = MSG_HEAD + 2 * L
    GROUP = max(R, NL, NA)  # rows a slot of the outbox grid holds
    K = GROUP * L + 1
    window = min(WINDOW, L)
    majority = NA // 2 + 1  # |waitfor| < NA / 2
    ANSWERS = (1 << NA) - 1  # a wait word's acceptor bits (the bug: a count)
    T0, T1, T2, T3 = BASE, BASE + L, BASE + 2 * L, BASE + 3 * L
    slots = jnp.arange(1, L + 1, dtype=jnp.int32)
    acc_bits = jnp.arange(NA, dtype=jnp.int32)
    # The outbox grid: row r is (slot r // GROUP + 1, member r % GROUP).
    grid_q = np.arange(GROUP * L, dtype=np.int32) // GROUP
    grid_i = np.arange(GROUP * L, dtype=np.int32) % GROUP
    # The groups a leader sends one header to (``bc_group``).
    TO_REPLICAS, TO_ACCEPTORS = 0, 1
    group_base = jnp.asarray([0, ACC0], jnp.int32)
    group_size = jnp.asarray([R, NA], jnp.int32)

    def init_state(actor_id: int) -> np.ndarray:
        s = np.zeros(S, np.int32)
        if actor_id < LEAD0:
            s[R_SLOT_IN] = s[R_SLOT_OUT] = 1
        elif actor_id < ACC0:
            s[L_BALLOT] = actor_id - LEAD0  # (0, self)
            s[L_SCOUTING] = 1
            s[T1 : T1 + L] = BOTTOM
        else:
            s[A_BALLOT] = BOTTOM
            s[T0 : T0 + L] = BOTTOM
        return s

    def initial_msgs(actor_id: int) -> np.ndarray:
        """A leader starts with a scout: P1A((0, self)) to every acceptor."""
        if not LEAD0 <= actor_id < ACC0:
            return np.zeros((0, 2 + W), np.int32)
        rows = np.zeros((NA, 2 + W), np.int32)
        rows[:, 0] = 1
        rows[:, 1] = ACC0 + np.arange(NA)
        rows[:, 2] = T_P1A
        rows[:, 3] = actor_id - LEAD0
        return rows

    # -- what a branch hands the outbox builder ------------------------------
    zero = jnp.int32(0)

    def sends(**kw):
        """One branch's sends, as a few scalars: a broadcast of one header
        to a group (``bc_*``), the adoption's burst (``burst``: P2A for
        every proposal of the new state), a replica's proposals
        (``prop_*``, [window] each) and the one reply or timer
        (``lone_*``; ``lone_table`` attaches the acceptor's table)."""
        out = dict(
            bc_valid=zero, bc_group=zero, bc_tag=zero, bc_f1=zero,
            bc_f2=zero, bc_f3=zero, burst=zero,
            prop_valid=jnp.zeros(window, jnp.int32),
            prop_slot=jnp.zeros(window, jnp.int32),
            prop_cmd=jnp.zeros(window, jnp.int32),
            lone_valid=zero, lone_dst=zero, lone_tag=zero, lone_f1=zero,
            lone_f2=zero, lone_f3=zero, lone_table=zero,
        )
        for key, value in kw.items():
            out[key] = jnp.asarray(value).astype(jnp.int32)
        return out

    def per_slot(vec):
        """[<= L] -> the grid's [GROUP * L]: entry q for every row of slot
        q (a broadcast and a reshape: no gather)."""
        vec = jnp.concatenate(
            [vec, jnp.zeros(L - vec.shape[0], jnp.int32)]
        )
        return jnp.broadcast_to(vec[:, None], (L, GROUP)).reshape(-1)

    def popcount(mask):
        return jnp.sum((mask >> acc_bits) & 1)

    # -- replica ------------------------------------------------------------
    def propose(st, enabled):
        """The paper's ``propose()``; returns the PROPOSE rows too."""
        slot_in, slot_out = st[R_SLOT_IN], st[R_SLOT_OUT]
        req, props, dec = (
            st[T0 : T0 + L], st[T1 : T1 + L], st[T2 : T2 + L]
        )
        # Every slot under slot_out is decided: the loop walks over them.
        slot_in = jnp.where(
            enabled & jnp.any(req != 0), jnp.maximum(slot_in, slot_out),
            slot_in,
        )
        valid, at, cmds = [], [], []
        for _ in range(window):
            can = (
                enabled & (slot_in < slot_out + WINDOW) & (slot_in <= L)
                & jnp.any(req != 0)
            )
            take = can & (vget(dec, slot_in - 1) == 0)
            c = jnp.min(jnp.where(req != 0, slots, L + 1))
            req = jnp.where((slots == c) & take, 0, req)
            props = vset(props, slot_in - 1, c, take)
            valid.append(take)
            at.append(slot_in)
            cmds.append(c)
            slot_in = slot_in + can.astype(jnp.int32)
        st = vset(st, R_SLOT_IN, slot_in)
        st = seg_set(seg_set(st, T0, req), T1, props)
        return st, dict(
            prop_valid=jnp.stack(valid), prop_slot=jnp.stack(at),
            prop_cmd=jnp.stack(cmds),
        )

    def on_request(me, st, snd, msg):
        c = msg[1]
        ok = (me < LEAD0) & (c >= 1) & (c <= L)
        st = seg_set(st, T0, st[T0 : T0 + L] | ((slots == c) & ok))
        st, rows = propose(st, me < LEAD0)
        return st, sends(**rows)

    def on_decision(me, st, snd, msg):
        s, c = msg[1], msg[2]
        rep = me < LEAD0
        ok = rep & (s >= 1) & (s <= L) & (c != 0)
        req, props, dec = (
            st[T0 : T0 + L], st[T1 : T1 + L], st[T2 : T2 + L]
        )
        held = vget(dec, s - 1)
        st = vset(
            st, R_CONFLICT, 1, ok & (held != 0) & (held != c)
        )
        dec = vset(dec, s - 1, c, ok & (held == 0))
        slot_out = st[R_SLOT_OUT]
        upto = jnp.min(
            jnp.where((dec == 0) & (slots >= slot_out), slots, L + 1)
        )
        ran = ok & (slots >= slot_out) & (slots < upto)
        # A proposal of ours that lost its slot goes back to requests.
        back = ran & (props != 0) & (props != dec)
        req = req | jnp.any(
            back[:, None] & (props[:, None] == slots[None, :]), axis=0
        )
        props = jnp.where(ran, 0, props)
        again = jnp.any(
            (dec[:, None] == dec[None, :])
            & (slots[None, :] < slots[:, None]),
            axis=1,
        )
        st = vset(
            st, R_PERFORMED,
            st[R_PERFORMED] + jnp.sum(ran & ~again), ok,
        )
        st = vset(st, R_SLOT_OUT, upto, ok)
        st = seg_set(seg_set(seg_set(st, T0, req), T1, props), T2, dec)
        st, rows = propose(st, rep)
        return st, sends(**rows)

    # -- leader ---------------------------------------------------------------
    def from_acceptor(snd):
        return (snd >= ACC0) & (snd < n)

    def answered(wait, snd, enabled):
        """``waitfor -= {a}``: the wait word with acceptor ``snd`` in it,
        and how many have answered. The bug keeps a count."""
        if bug == "count_replies":
            # BUG: a reply heard twice counts twice.
            new = wait + 1
            count = new & ANSWERS
        else:
            new = wait | (jnp.int32(1) << jnp.clip(snd - ACC0, 0, NA - 1))
            count = popcount(new & ANSWERS)
        return jnp.where(enabled, new, wait), count

    def commander(ballot):
        """A new commander's word: its ballot, nobody has answered."""
        return (ballot + 1) << NA

    def preempted(me, st, b, cond):
        """The paper's ``preempted(b')``, and the BACKOFF timer."""
        go = cond & (b > st[L_BALLOT])
        ballot = (b // NL + 1) * NL + (me - LEAD0)
        new = vset(st, L_ACTIVE, 0)
        new = vset(new, L_SCOUTING, 0)
        new = vset(new, L_BALLOT, ballot)
        new = vset(new, L_PREEMPTS, st[L_PREEMPTS] + 1)
        return jnp.where(go, new, st), dict(
            lone_valid=go, lone_dst=me, lone_tag=T_BACKOFF, lone_f1=ballot,
        )

    def on_propose(me, st, snd, msg):
        s, c = msg[1], msg[2]
        lead = (me >= LEAD0) & (me < ACC0)
        rec = (
            lead & (s >= 1) & (s <= L) & (c != 0)
            & (vget(st[T0 : T0 + L], s - 1) == 0)
        )
        st = vset(st, T0 + s - 1, c, rec)
        spawn = rec & (st[L_ACTIVE] != 0)
        st = vset(st, T3 + s - 1, commander(st[L_BALLOT]), spawn)
        return st, sends(
            bc_valid=spawn, bc_group=TO_ACCEPTORS, bc_tag=T_P2A,
            bc_f1=st[L_BALLOT], bc_f2=s, bc_f3=c,
        )

    def on_p1b(me, st, snd, msg):
        b, asked = msg[1], msg[2]
        lead = (me >= LEAD0) & (me < ACC0)
        mine = (
            lead & (st[L_SCOUTING] != 0) & from_acceptor(snd)
            & (asked == st[L_BALLOT])
        )
        match = mine & (b == st[L_BALLOT])
        tb, tc = msg[MSG_HEAD : MSG_HEAD + L], msg[MSG_HEAD + L :]
        pb, pc = st[T1 : T1 + L], st[T2 : T2 + L]
        better = match & (tb > pb)
        pb, pc = jnp.where(better, tb, pb), jnp.where(better, tc, pc)
        wait, count = answered(st[L_SCOUT_WAIT], snd, match)
        st = vset(st, L_SCOUT_WAIT, wait)
        st = seg_set(seg_set(st, T1, pb), T2, pc)
        adopted = match & (count >= majority)
        props = jnp.where(pb >= 0, pc, st[T0 : T0 + L])  # <| pmax
        new = seg_set(st, T0, props)
        new = seg_set(
            new, T3, jnp.where(props != 0, commander(st[L_BALLOT]), 0)
        )
        new = vset(new, L_ACTIVE, 1)
        new = vset(new, L_SCOUTING, 0)
        new = vset(new, L_ADOPTIONS, st[L_ADOPTIONS] + 1)
        st = jnp.where(adopted, new, st)
        st, timer = preempted(me, st, b, mine & ~match)
        return st, sends(burst=adopted, **timer)

    def on_p2b(me, st, snd, msg):
        b, s, asked = msg[1], msg[2], msg[3]
        lead = (me >= LEAD0) & (me < ACC0)
        in_range = (s >= 1) & (s <= L)
        word = vget(st[T3 : T3 + L], s - 1)
        ballot = (word >> NA) - 1  # the commander's
        mine = (
            lead & from_acceptor(snd) & in_range & (word != 0)
            & (asked == ballot)
        )
        match = mine & (b == ballot)
        word, count = answered(word, snd, match)
        decide = match & (count >= majority)
        # Done once it decides, and once it hears a higher ballot.
        done = decide | (mine & (b > ballot))
        st = vset(st, T3 + s - 1, jnp.where(done, 0, word), mine)
        st, timer = preempted(me, st, b, mine & ~match)
        return st, sends(
            bc_valid=decide, bc_group=TO_REPLICAS, bc_tag=T_DECISION,
            bc_f1=s, bc_f2=vget(st[T0 : T0 + L], s - 1), **timer
        )

    def on_backoff(me, st, snd, msg):
        lead = (me >= LEAD0) & (me < ACC0)
        go = lead & (st[L_ACTIVE] == 0) & (st[L_SCOUTING] == 0)
        new = vset(st, L_SCOUTING, 1)
        new = vset(new, L_SCOUT_WAIT, 0)
        new = seg_set(new, T1, jnp.full(L, BOTTOM, jnp.int32))
        new = seg_set(new, T2, jnp.zeros(L, jnp.int32))
        st = jnp.where(go, new, st)
        return st, sends(
            bc_valid=go, bc_group=TO_ACCEPTORS, bc_tag=T_P1A,
            bc_f1=st[L_BALLOT],
        )

    # -- acceptor -------------------------------------------------------------
    def from_leader(snd):
        return (snd >= LEAD0) & (snd < ACC0)

    def on_p1a(me, st, snd, msg):
        b = msg[1]
        acc = me >= ACC0
        st = vset(st, A_BALLOT, b, acc & (b > st[A_BALLOT]))
        return st, sends(
            lone_valid=acc & from_leader(snd), lone_dst=snd, lone_tag=T_P1B,
            lone_f1=st[A_BALLOT], lone_f2=b, lone_table=1,
        )

    def on_p2a(me, st, snd, msg):
        b, s, c = msg[1], msg[2], msg[3]
        acc = me >= ACC0
        take = acc & (b == st[A_BALLOT]) & (s >= 1) & (s <= L)
        st = vset(st, T0 + s - 1, b, take)
        st = vset(st, T1 + s - 1, c, take)
        return st, sends(
            lone_valid=acc & from_leader(snd), lone_dst=snd, lone_tag=T_P2B,
            lone_f1=st[A_BALLOT], lone_f2=s, lone_f3=b,
        )

    branches = [
        on_request, on_propose, on_decision, on_p1a, on_p1b, on_p2a, on_p2b,
        on_backoff,
    ]

    def handler(actor_id, state, snd, msg):
        # Every branch is gated on the role it belongs to. A branch hands
        # back a few scalars; the [K, 2 + W] outbox is built once, here.
        tag = jnp.clip(msg[0], 1, NUM_TAGS) - 1
        state, out = jax.lax.switch(
            tag, branches, actor_id, state, snd, msg
        )
        q, i = jnp.asarray(grid_q), jnp.asarray(grid_i)
        # The adoption's burst: P2A(ballot, q + 1, proposal) to acceptor i.
        proposal = per_slot(state[T0 : T0 + L])
        burst = (out["burst"] != 0) & (proposal != 0) & (i < NA)
        # One header to every member of a group: the grid's first slot.
        base = vget(group_base, out["bc_group"])
        size = vget(group_size, out["bc_group"])
        bcast = (out["bc_valid"] != 0) & (q == 0) & (i < size)
        # A replica's proposals: the j-th to every leader, in slot j.
        prop = (per_slot(out["prop_valid"]) != 0) & (i < NL)
        head = jnp.stack([
            (burst | bcast | prop).astype(jnp.int32),
            jnp.where(burst, ACC0 + i, jnp.where(bcast, base + i, LEAD0 + i)),
            jnp.where(
                burst, T_P2A, jnp.where(bcast, out["bc_tag"], T_PROPOSE)
            ),
            jnp.where(
                burst, state[L_BALLOT],
                jnp.where(bcast, out["bc_f1"], per_slot(out["prop_slot"])),
            ),
            jnp.where(
                burst, q + 1,
                jnp.where(bcast, out["bc_f2"], per_slot(out["prop_cmd"])),
            ),
            jnp.where(burst, proposal, jnp.where(bcast, out["bc_f3"], 0)),
        ], axis=1)
        grid = jnp.concatenate(
            [head, jnp.zeros((GROUP * L, 2 * L), jnp.int32)], axis=1
        )
        lone = jnp.concatenate([
            jnp.stack([
                out["lone_valid"], jnp.clip(out["lone_dst"], 0, n - 1),
                out["lone_tag"], out["lone_f1"], out["lone_f2"],
                out["lone_f3"],
            ]),
            jnp.where(out["lone_table"] != 0, state[T0 : T0 + 2 * L], 0),
        ])
        return state, jnp.concatenate([grid, lone[None, :]])

    # -- invariant ------------------------------------------------------------
    words, ids = np.arange(S), np.arange(n)
    decision_word = (words >= T2) & (words < T2 + L)
    replica_row = ids < LEAD0

    def invariant(states, alive):
        # Whole [N, S] rows under constant masks against one reference
        # row; no slice, no column read (DESIGN.md sec. 3, PR 41).
        rep = (alive & replica_row)[:, None]
        decided = jnp.where(rep & decision_word[None, :], states, 0)
        # If the replicas that hold a slot agree, its largest entry is
        # every holder's entry.
        reference = jnp.max(decided, axis=0)
        differ = jnp.any((decided != 0) & (decided != reference[None, :]))
        handed_both = jnp.any(
            rep & (words == R_CONFLICT)[None, :] & (states != 0)
        )
        return jnp.where(differ | handed_both, jnp.int32(1), 0)

    # -- progress counts (DSLApp.progress) --------------------------------------
    leader_row = (ids >= LEAD0) & (ids < ACC0)

    def committed(states):
        return jnp.max(jnp.where(replica_row, states[:, R_SLOT_OUT] - 1, 0))

    def of_leaders(word):
        return lambda states: jnp.sum(
            jnp.where(leader_row, states[:, word], 0)
        )

    progress = (
        ("committed", committed),
        ("adoptions", of_leaders(L_ADOPTIONS)),
        ("preempts", of_leaders(L_PREEMPTS)),
    )

    return DSLApp(
        name=name,
        num_actors=n,
        state_width=S,
        msg_width=W,
        max_outbox=K,
        init_state=init_state,
        handler=handler,
        initial_msgs=initial_msgs,
        invariant=invariant,
        timer_tags=(T_BACKOFF,),
        tag_names=(
            "", "Request", "Propose", "Decision", "P1a", "P1b", "P2a",
            "P2b", "Backoff",
        ),
        progress=progress,
        channels="datagram",
    )


def request(app: DSLApp, command: int) -> tuple:
    """REQUEST(command) as it travels: every message is ``msg_width``
    words (the host tier hands a handler the tuple as it was sent)."""
    return (T_REQUEST, command) + (0,) * (app.msg_width - 2)


class PaxosClient:
    """The clients, as the fuzzer's send generator: every command k = 1,
    2, ... is sent to every replica (PMMC's clients broadcast a request
    to all replicas), one external send each in consecutive draws, so
    waits and kills fall between them. There are as many clients as
    replicas, each nearest another replica, so that a round's commands
    reach the replicas in different orders, as concurrent clients' do:
    of commands R j + 1 .. R j + R, replica i is sent command R j + 1 +
    (i + p) mod R in pass p = 0 .. R - 1 (R = 3: 1 to replica 0, 2 to 1,
    3 to 2; then 2, 3, 1; then 3, 1, 2). Replicas therefore propose
    different commands for one slot: what consensus is for. A replica
    that is down is passed over. It draws nothing from ``rng``: a
    program's faults and waits are the fuzz, and the sends are a
    function of them."""

    def __init__(self, app: DSLApp):
        self.app = app
        replicas, _leaders, _acceptors = role_counts(app.num_actors)
        self.replicas = app.actor_names()[:replicas]
        self.reset()

    def reset(self) -> None:
        self.at = 0  # sends drawn so far, those passed over too

    def generate_row(self, rng: _random.Random, alive):
        if not any(name in alive for name in self.replicas):
            return None
        count = len(self.replicas)
        while True:
            round_, at = divmod(self.at, count * count)
            pass_, replica = divmod(at, count)
            self.at += 1
            name = self.replicas[replica]
            if name in alive:
                command = count * round_ + 1 + (replica + pass_) % count
                return name, request(self.app, command)

    def generate(self, rng: _random.Random, alive):
        row = self.generate_row(rng, alive)
        return None if row is None else Send(row[0], constant_message(row[1]))


def paxos_send_generator(app: DSLApp) -> PaxosClient:
    return PaxosClient(app)
