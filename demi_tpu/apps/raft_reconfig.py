"""Raft as the dissertation has it, in the dual host/device DSL: persistent
state, single-server membership changes and InstallSnapshot.

Ongaro, *Consensus: Bridging Theory and Practice* (Stanford, 2014): ch. 3
with fig. 3.1 (basic Raft and its persistent state), ch. 4 with fig. 4.1
(``AddServer`` / ``RemoveServer`` one server at a time; 4.2.1 catching a
new server up as a non-voter, 4.2.2 removing the current leader, 4.2.3
disruptive servers, 4.4 a server with no configuration never stands), ch. 5
with fig. 5.3 (memory-based snapshots and ``InstallSnapshot``, the snapshot
carrying the latest configuration), the no-op a leader appends at the start
of its term (6.4), and Ongaro's raft-dev post of 10 July 2015, "bug in
single-server membership changes", with its fix: a leader may not append a
configuration entry until it has committed an entry of its own term.
``apps/raft.py`` is the other raft (static membership, no disk, one entry
an AppendEntries); the cells that rest on its amnesia do not move.

``n`` servers, the first ``members`` of them (``n - 2``) the boot
configuration: each starts with one configuration entry at index 1 (term 0,
committed: 4.4's bootstrap). The others are spares: empty log, no
configuration; they never stand and count in no quorum until a
configuration that names them is in their log.

A server's configuration is the latest configuration entry in its own log,
committed or not (4.1), else its snapshot's, else none; it is recomputed
whenever a delivery begins and ends, so a truncated entry falls back. The
log is a window ``[LOG_BASE + 1 .. LOG_BASE + LOG_LEN]`` above a snapshot:
a server that has applied ``snapshot_every`` entries above ``LOG_BASE``
snapshots through ``APPLIED`` on its own (5.1) and the window shifts down,
so a schedule commits several times ``log_cap``. An entry is ``(term,
kind, value)``: ``NOOP``, ``CMD`` (value = key * 2^16 + v over 8 registers)
or ``CFG`` (value = the member mask).

Messages, ``msg_width`` 18 (``i`` is the sender):

  T_ELECTION (timer)       no configuration, or not in it: nothing (4.4).
                           A leader: nothing. HEARD set: clear it.
                           Otherwise stand: TERM + 1, vote for itself,
                           REQ_VOTE to the other members of its CFG.
  T_HEARTBEAT (timer)      a leader works off its membership change, sends
                           every member and the catch-up target what it
                           lacks (below), and re-arms.
  REQ_VOTE(t, lastIdx,     HEARD set: dropped whole, the term not adopted
           lastTerm)       (4.2.3). Else fig. 3.1: grant if t is current,
                           VOTED_FOR is none or i, and i's log is at least
                           as up to date. The receiver's configuration is
                           not consulted (4.1).
  VOTE_REPLY(t, granted)   a candidate counts i; votes of a majority of
                           its own CFG make it leader: NEXT = last + 1,
                           MATCH = 0, a NOOP of its term appended (6.4),
                           APPEND to every member.
  APPEND(t, prevIdx,       fig. 3.1's five steps over the window; up to 4
         prevTerm, commit, entries. prevIdx below LOG_BASE skips what the
         n, entries[<=4])  snapshot covers; an entry that does not fit the
                           window is refused until the server has compacted.
  APPEND_REPLY(t, ok, m)   ok: MATCH[i] = max(MATCH[i], m), NEXT = MATCH + 1.
                           Not: NEXT[i] = min(NEXT[i] - 1, m + 1), m the
                           follower's last index (3.5's optimisation).
  INSTALL_SNAPSHOT(t,      fig. 5.3 in one chunk: a snapshot no newer than
      lastIdx, lastTerm,   its own is acknowledged and nothing changes; a
      lastCfg, digest,     window that holds lastIdx with lastTerm keeps what
      reg[8])              follows it (step 6); else the log goes (step 7),
                           the state machine is the snapshot's and lastCfg
                           the configuration (step 8).
  SNAPSHOT_REPLY(t, idx)   MATCH[i] = max(MATCH[i], idx), NEXT = MATCH + 1.
  CLIENT(key, v)           a leader with room appends CMD; another forwards
                           what the client sent it to LEADER_HINT, once.
  ADMIN(op, server)        fig. 4.1. A non-leader forwards once. A leader
                           with PENDING set drops it; a change that is
                           already so does nothing; else PENDING is set.

Every message with a higher term makes a follower of its receiver (fig.
3.1; a leader that loses its role drops PENDING). *What a server lacks*:
where NEXT[i] <= LOG_BASE an INSTALL_SNAPSHOT, else an APPEND of the up to
4 entries from NEXT[i], or an empty one. They go out from the final state
of the delivery: on a heartbeat, on an election won, on a CLIENT or
configuration entry appended (to all), and to the sender of a reply that is
still behind.

*After every delivery at a leader*: COMMIT rises to the largest N with
LOG[N].term = TERM and MATCH >= N on a majority of its current CFG, itself
counted only where it is a member (4.2.2). *PENDING* is worked off at each
heartbeat, reply and ADMIN taken: AddServer first catches the server up
(4.2.1): a round ends when MATCH reaches the last index the leader had at
its start; the change goes on once a round ends during which at most 4
entries were appended; a round that ends later, or a heartbeat that finds
it unfinished, counts, and past 10 the request is dropped. Then, for both:
wait until the latest configuration entry is committed (CFG_IDX <= COMMIT)
and until an entry of the leader's own term is committed (the 2015 fix),
append CFG = old +- server, and use it at once.

*After every delivery at any server*: COMMIT > APPLIED applies (CMD writes
its register, every kind advances DIGEST and RING); a CFG entry applied at
a leader counts CFG_COMMITTED, and a leader that is not in it steps down
(4.2.2). Then compaction, where due.

``DSLApp.durable``: TERM, VOTED_FOR, the window, the snapshot and five
ghost counts: 115 of 216 words at ``log_cap`` 32 (and the runtime's count
of lives, ``DSLApp.spawn_count``). A ``HardKill`` + ``Start``
is a recovery from disk: the first delivery after it loads the snapshot
into the state machine (APPLIED < LOG_BASE) and re-applies from there.

Safety invariants, after every delivery:
  code 1 -- Election Safety: two live servers are leaders in one term.
  code 2 -- State Machine Safety: two live servers hold different digests
            for an index both have applied and both still have in their
            rings (index i is in a's ring if max(APPLIED - 64, RING_FROM)
            < i <= APPLIED; RING_FROM is where this life's applying began).

Seeded bugs:
  bug="reconfig_before_noop"   -- fig. 4.1 as printed: the wait for an
                                  entry of the leader's own term is left out.
  bug="snapshot_keeps_config"  -- fig. 5.3's step 8 without its last clause:
                                  the configuration below the window is a
                                  word in memory (BASE_CFG) that an installed
                                  snapshot does not set and a restart resets
                                  to the boot mask.
"""

from __future__ import annotations

import random as _random
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..dsl import DSLApp, row_set, vgather, vget
from ..external_events import OP_START, Send, constant_message

# Message tags.
T_ELECTION = 1  # timer
T_HEARTBEAT = 2  # timer
T_REQ_VOTE = 3  # (tag, term, last_idx, last_term)
T_VOTE_REPLY = 4  # (tag, term, granted)
T_APPEND = 5  # (tag, term, prev_idx, prev_term, commit, n, 4 x (term, kind, value))
T_APPEND_REPLY = 6  # (tag, term, success, match_idx)
T_INSTALL = 7  # (tag, term, last_idx, last_term, last_cfg, digest, reg[8])
T_SNAP_REPLY = 8  # (tag, term, last_idx)
T_CLIENT = 9  # (tag, key, v)
T_ADMIN = 10  # (tag, op, server)
NUM_TAGS = 10

APPEND_HEAD = 6
BATCH = 4  # entries an APPEND carries
MSG_W = APPEND_HEAD + 3 * BATCH
KEYS = 8  # registers of the state machine
RING_LEN = 64  # digests the invariant's ghost history keeps
CATCHUP_ROUNDS = 10  # 4.2.1's own number
BUGS = (None, "reconfig_before_noop", "snapshot_keeps_config")

# Entry kinds, roles, membership ops.
K_NOOP, K_CMD, K_CFG = 0, 1, 2
FOLLOWER, CANDIDATE, LEADER = 0, 1, 2
ADD, REMOVE = 1, 2

# State layout: the scalars, then SNAP_REG[8], LOG_T[L], LOG_K[L], LOG_V[L],
# NEXT[n], MATCH[n], REG[8], RING[64].
TERM = 0  # durable (fig. 3.1)
VOTED_FOR = 1  # durable; -1 = none
LOG_LEN = 2  # durable: entries in the window
LOG_BASE = 3  # durable: the snapshot's lastIndex
SNAP_TERM = 4  # durable
SNAP_CFG = 5  # durable: the snapshot's configuration mask
SNAP_DIGEST = 6  # durable
CFG_COMMITTED = 7  # durable ghost: CFG entries applied as leader
COMPACTIONS = 8  # durable ghost
SNAP_SENT = 9  # durable ghost
SNAP_INSTALLED = 10  # durable ghost
RESTORES = 11  # DSLApp.spawn_count: 1 in a first life
ROLE = 12
COMMIT = 13
APPLIED = 14
VOTES = 15  # mask of votes granted
LEADER_HINT = 16  # -1 = unknown
DIGEST = 17
HEARD = 18  # heard from a leader since the last election-timer delivery
PENDING = 19  # op * 8 + server; 0 = none
CATCHUP_GOAL = 20
CATCHUP_ROUND = 21  # 0 = not catching up
CFG = 22  # derived: the mask in force
CFG_IDX = 23  # derived: its entry's index (LOG_BASE where the snapshot's)
BASE_CFG = 24  # the configuration below the window as memory has it
RING_FROM = 25  # ghost: the index this life's applying began above
SNAP_REG = 26

_SCALARS = (
    "term", "voted_for", "log_len", "log_base", "snap_term", "snap_cfg",
    "snap_digest", "cfg_committed", "compactions", "snap_sent",
    "snap_installed", "restores", "role", "commit", "applied", "votes",
    "leader_hint", "digest", "heard", "pending", "catchup_goal",
    "catchup_round", "cfg", "cfg_idx", "base_cfg", "ring_from",
)
assert len(_SCALARS) == SNAP_REG

# The digest's mix, the same in the reference: d' = d * _P + h(index, kind,
# value) in wrapping int32.
_P = 1000003


def _wrap(x: int) -> int:
    x &= 0xFFFFFFFF
    return x - (1 << 32) if x >= 1 << 31 else x


def entry_hash(index, kind, value):
    """h(index, kind, value) in wrapping int32 (ints or traced)."""
    return (index * 8191 + kind) * 131071 + value * 31 + 7


def state_layout(n: int, log_cap: int) -> dict:
    """Word offsets of a server's row: ``{name: (start, length)}``."""
    L = log_cap
    layout = {name.upper(): (i, 1) for i, name in enumerate(_SCALARS)}
    at = SNAP_REG
    for name, length in (
        ("SNAP_REG", KEYS), ("LOG_T", L), ("LOG_K", L), ("LOG_V", L),
        ("NEXT", n), ("MATCH", n), ("REG", KEYS), ("RING", RING_LEN),
    ):
        layout[name] = (at, length)
        at += length
    layout["width"] = (at, 0)
    return layout


def state_width(n: int, log_cap: int) -> int:
    return state_layout(n, log_cap)["width"][0]


def durable_words(n: int, log_cap: int) -> tuple:
    """What is on disk (fig. 3.1's persistent state and the snapshot) and
    the ghost counts; RESTORES is the runtime's (``spawn_count``)."""
    lay = state_layout(n, log_cap)
    words = list(range(TERM, SNAP_INSTALLED + 1))
    for name in ("SNAP_REG", "LOG_T", "LOG_K", "LOG_V"):
        start, length = lay[name]
        words += range(start, start + length)
    return tuple(words)


def make_raft_reconfig_app(
    num_actors: int = 7,
    log_cap: int = 32,
    snapshot_every: Optional[int] = None,
    bug: Optional[str] = None,
    name: str = "g",
    members: Optional[int] = None,
) -> DSLApp:
    n, L = num_actors, log_cap
    members = n - 2 if members is None else members
    if snapshot_every is None:
        snapshot_every = L // 2
    if not 3 <= n <= 8:
        raise ValueError("raft_reconfig needs 3..8 servers (PENDING = op * 8 + server)")
    if not 1 <= members <= n:
        raise ValueError(f"the boot configuration holds 1..{n} servers")
    if not BATCH <= L <= RING_LEN:
        raise ValueError(f"log_cap must lie in {BATCH}..{RING_LEN}")
    if not 1 <= snapshot_every <= L:
        raise ValueError("snapshot_every must lie in 1..log_cap")
    if bug not in BUGS:
        raise ValueError(
            f"unknown raft_reconfig bug {bug!r} (choices: {BUGS[1:]})"
        )
    lay = state_layout(n, L)
    S = lay["width"][0]
    W = MSG_W
    K = n + 1  # outbox rows: one a server, and one more for a timer
    boot_mask = (1 << members) - 1
    ids = jnp.arange(n, dtype=jnp.int32)
    slots = jnp.arange(L, dtype=jnp.int32)
    arrays = tuple(
        (key.lower(), lay[key][0], lay[key][1])
        for key in ("SNAP_REG", "LOG_T", "LOG_K", "LOG_V", "NEXT", "MATCH",
                    "REG", "RING")
    )
    # POW[k] = _P ** k, and the lower-triangular [L, L] of _P ** (j - m).
    pow_np = np.asarray([_wrap(pow(_P, k, 1 << 32)) for k in range(L + 1)],
                        np.int32)
    tri_np = np.zeros((L, L), np.int32)
    for j in range(L):
        tri_np[j, : j + 1] = pow_np[: j + 1][::-1]

    def init_state(actor_id: int) -> np.ndarray:
        s = np.zeros(S, np.int32)
        s[VOTED_FOR] = -1
        s[LEADER_HINT] = -1
        if actor_id < members:
            s[LOG_LEN] = 1
            s[lay["LOG_K"][0]] = K_CFG
            s[lay["LOG_V"][0]] = boot_mask
            s[COMMIT] = 1
            s[CFG] = s[BASE_CFG] = boot_mask
            s[CFG_IDX] = 1
        return s

    def initial_msgs(actor_id: int) -> np.ndarray:
        rows = np.zeros((1, 2 + W), np.int32)
        rows[0, :3] = (1, actor_id, T_ELECTION)
        return rows

    # -- the row as a dict of scalars and arrays ---------------------------
    def unpack(state):
        s = {key: state[i] for i, key in enumerate(_SCALARS)}
        for key, start, length in arrays:
            s[key] = state[start : start + length]
        return s

    def pack(s):
        head = jnp.stack([s[key] for key in _SCALARS]).astype(jnp.int32)
        return jnp.concatenate([head] + [s[key] for key, _, _ in arrays])

    def choose(cond, new, old):
        """``new`` where ``cond``, field by field; a field both hold as one
        array needs no select (and none is traced for it)."""
        return {
            key: old[key] if new[key] is old[key]
            else jnp.where(cond, new[key], old[key])
            for key in old
        }

    def bit(i):
        return jnp.int32(1) << i

    def has(mask, i):
        return ((mask >> i) & 1) == 1

    def popcount(mask):
        return jnp.sum((mask >> ids) & 1)

    def quorum(mask):
        return popcount(mask) // 2 + 1

    def last_index(s):
        return s["log_base"] + s["log_len"]

    def at_slot(vec, slot):
        return jnp.sum(jnp.where(slots == slot, vec, 0))

    def term_at(s, idx):
        """The term of entry ``idx``: the snapshot's at LOG_BASE, the
        window's above it, -1 where this server does not know."""
        slot = idx - s["log_base"] - 1
        held = (slot >= 0) & (slot < s["log_len"])
        return jnp.where(
            held, at_slot(s["log_t"], slot),
            jnp.where(idx == s["log_base"], s["snap_term"], -1),
        )

    def below_window(s):
        """The configuration below the window."""
        if bug == "snapshot_keeps_config":
            return s["base_cfg"]  # BUG: memory's word, not the snapshot's
        return s["snap_cfg"]

    def latest_cfg(s, upto=None):
        """``(mask, index)`` of the latest configuration entry in the
        window (at or below ``upto``), else what lies below the window."""
        held = (slots < s["log_len"]) & (s["log_k"] == K_CFG)
        if upto is not None:
            held = held & (s["log_base"] + 1 + slots <= upto)
        j = jnp.max(jnp.where(held, slots, -1))
        return (
            jnp.where(j >= 0, at_slot(s["log_v"], j), below_window(s)),
            jnp.where(j >= 0, s["log_base"] + 1 + j, s["log_base"]),
        )

    def refresh_cfg(s):
        mask, idx = latest_cfg(s)
        return {**s, "cfg": mask, "cfg_idx": idx}

    def append_entry(s, can, term, kind, value):
        """One entry at the window's end, where ``can`` (the caller has
        looked for room)."""
        hit = (slots == s["log_len"]) & can
        return {
            **s,
            "log_t": jnp.where(hit, term, s["log_t"]),
            "log_k": jnp.where(hit, kind, s["log_k"]),
            "log_v": jnp.where(hit, value, s["log_v"]),
            "log_len": s["log_len"] + can.astype(jnp.int32),
        }

    def shifted(s, k):
        """The window with its first ``k`` entries gone."""
        src = slots + k
        return {
            **s,
            "log_t": vgather(s["log_t"], src),
            "log_k": vgather(s["log_k"], src),
            "log_v": vgather(s["log_v"], src),
            "log_len": jnp.maximum(s["log_len"] - k, 0),
        }

    def recover(s):
        """The first delivery after a restart: the state machine is the
        snapshot's (fig. 5.3's state is on disk, REG and APPLIED are not)."""
        behind = s["applied"] < s["log_base"]
        return {
            **s,
            "reg": jnp.where(behind, s["snap_reg"], s["reg"]),
            "digest": jnp.where(behind, s["snap_digest"], s["digest"]),
            "applied": jnp.where(behind, s["log_base"], s["applied"]),
            "commit": jnp.where(
                behind, jnp.maximum(s["commit"], s["log_base"]), s["commit"]
            ),
            "ring_from": jnp.where(behind, s["log_base"], s["ring_from"]),
        }

    def step_down(s, term):
        """Fig. 3.1, all servers: a higher term makes a follower."""
        newer = term > s["term"]
        return choose(newer, {
            **s, "term": term, "voted_for": jnp.int32(-1),
            "role": jnp.int32(FOLLOWER), "votes": jnp.int32(0),
            "leader_hint": jnp.int32(-1), "pending": jnp.int32(0),
            "catchup_round": jnp.int32(0),
        }, s)

    def become_leader(actor_id, s):
        last = last_index(s)
        s = {
            **s, "role": jnp.int32(LEADER), "leader_hint": jnp.int32(actor_id),
            "next": jnp.full((n,), last + 1, jnp.int32),
            "match": jnp.zeros((n,), jnp.int32),
            "pending": jnp.int32(0), "catchup_round": jnp.int32(0),
        }
        # 6.4: a no-op of its term, so that it learns what is committed.
        return append_entry(s, s["log_len"] < L, s["term"], K_NOOP, 0)

    def targets(actor_id, s):
        """Whom a leader replicates to: its CFG and the catch-up target,
        but itself."""
        adding = s["pending"] // 8 == ADD
        learner = jnp.where(adding, bit(s["pending"] % 8), 0)
        return (s["cfg"] | learner) & ~bit(actor_id)

    # -- the outbox --------------------------------------------------------
    def empty_outbox():
        return jnp.zeros((K, 2 + W), jnp.int32)

    def row(valid, dst, *fields):
        head = jnp.stack([
            jnp.asarray(x, jnp.int32) for x in (valid, dst) + fields
        ])
        return jnp.concatenate(
            [head, jnp.zeros(2 + W - head.shape[0], jnp.int32)]
        )

    def put(outbox, slot, valid, dst, *fields):
        return row_set(outbox, slot, row(valid, dst, *fields), valid)

    def to_mask(mask, enabled, tag, f1=0, f2=0, f3=0):
        """Row i to server i, for every i in ``mask``."""
        valid = (has(mask, ids) & enabled).astype(jnp.int32)
        zeros = jnp.zeros(n, jnp.int32)
        head = jnp.stack(
            [valid, ids, zeros + tag, zeros + f1, zeros + f2, zeros + f3],
            axis=1,
        )
        rows = jnp.concatenate(
            [head, jnp.zeros((n, 2 + W - 6), jnp.int32)], axis=1
        )
        return jnp.concatenate([rows, jnp.zeros((1, 2 + W), jnp.int32)])

    def replication_rows(s, send):
        """What each server in ``send`` lacks, from this state: rows
        ``[n, 2 + W]`` and how many of them are snapshots."""
        base, last = s["log_base"], last_index(s)
        nxt = s["next"]
        snap = nxt <= base
        prev = nxt - 1
        prev_slot = prev - base - 1
        prev_term = jnp.where(
            prev == base, s["snap_term"], vgather(s["log_t"], prev_slot)
        )
        count = jnp.clip(last - prev, 0, BATCH)
        k = jnp.arange(BATCH, dtype=jnp.int32)
        src = (prev_slot[:, None] + 1 + k[None, :]).reshape(-1)
        sent = (k[None, :] < count[:, None])[:, :, None]
        entries = jnp.stack(
            [vgather(s[key], src).reshape(n, BATCH)
             for key in ("log_t", "log_k", "log_v")],
            axis=2,
        )
        entries = jnp.where(sent, entries, 0).reshape(n, 3 * BATCH)
        valid = has(send, ids)
        zeros = jnp.zeros(n, jnp.int32)
        head = jnp.stack([valid.astype(jnp.int32), ids], axis=1)
        append = jnp.concatenate([
            head,
            jnp.stack([zeros + T_APPEND, zeros + s["term"], prev, prev_term,
                       zeros + s["commit"], count], axis=1),
            entries,
        ], axis=1)
        install = jnp.concatenate([
            head,
            jnp.stack([zeros + T_INSTALL, zeros + s["term"], zeros + base,
                       zeros + s["snap_term"], zeros + s["snap_cfg"],
                       zeros + s["snap_digest"]], axis=1),
            jnp.broadcast_to(s["snap_reg"][None, :], (n, KEYS)),
            jnp.zeros((n, W - 6 - KEYS), jnp.int32),
        ], axis=1)
        rows = jnp.where(snap[:, None], install, append)
        rows = jnp.where(valid[:, None], rows, 0)
        return rows, jnp.sum(valid & snap)

    # -- per-tag handlers: (s, outbox, send mask, work) --------------------
    NO = jnp.int32(0)

    def on_election(actor_id, s, snd, msg):
        can = has(s["cfg"], actor_id) & (s["role"] != LEADER)  # 4.4
        stand = can & (s["heard"] == 0)
        s = {**s, "heard": jnp.where(can, 0, s["heard"])}
        s = choose(stand, {
            **s, "term": s["term"] + 1, "role": jnp.int32(CANDIDATE),
            "voted_for": jnp.int32(actor_id), "votes": bit(actor_id),
            "leader_hint": jnp.int32(-1),
        }, s)
        last = last_index(s)
        out = to_mask(
            s["cfg"] & ~bit(actor_id), stand, T_REQ_VOTE, s["term"], last,
            term_at(s, last),
        )
        alone = stand & (quorum(s["cfg"]) <= 1)
        s = choose(alone, become_leader(actor_id, s), s)
        out = put(out, actor_id, True, actor_id, T_ELECTION)
        out = put(out, n, alone, actor_id, T_HEARTBEAT)
        return s, out, jnp.where(alone, targets(actor_id, s), 0), NO

    def on_heartbeat(actor_id, s, snd, msg):
        lead = s["role"] == LEADER
        out = put(empty_outbox(), actor_id, lead, actor_id, T_HEARTBEAT)
        return (
            s, out, jnp.where(lead, targets(actor_id, s), 0),
            jnp.where(lead, 2, 0),
        )

    def on_request_vote(actor_id, s, snd, msg):
        term, lli, llt = msg[1], msg[2], msg[3]
        deaf = s["heard"] != 0  # 4.2.3
        s = choose(deaf, s, step_down(s, term))
        mine = last_index(s)
        mine_t = term_at(s, mine)
        log_ok = (llt > mine_t) | ((llt == mine_t) & (lli >= mine))
        free = (s["voted_for"] == -1) | (s["voted_for"] == snd)
        grant = ~deaf & (term == s["term"]) & free & log_ok
        s = {**s, "voted_for": jnp.where(grant, snd, s["voted_for"])}
        out = put(
            empty_outbox(), 0, ~deaf, jnp.clip(snd, 0, n - 1), T_VOTE_REPLY,
            s["term"], grant,
        )
        return s, out, NO, NO

    def on_vote_reply(actor_id, s, snd, msg):
        term, granted = msg[1], msg[2]
        s = step_down(s, term)
        count = (
            (s["role"] == CANDIDATE) & (term == s["term"]) & (granted != 0)
        )
        votes = jnp.where(
            count, s["votes"] | bit(jnp.clip(snd, 0, n - 1)), s["votes"]
        )
        s = {**s, "votes": votes}
        wins = count & (popcount(votes & s["cfg"]) >= quorum(s["cfg"]))
        s = choose(wins, become_leader(actor_id, s), s)
        out = put(empty_outbox(), n, wins, actor_id, T_HEARTBEAT)
        return s, out, jnp.where(wins, targets(actor_id, s), 0), NO

    def from_leader(s, snd, term):
        """A request of a current leader: a candidate yields, the sender
        is the leader, and it has been heard."""
        current = term == s["term"]
        return {
            **s,
            "role": jnp.where(
                current & (s["role"] == CANDIDATE), FOLLOWER, s["role"]
            ),
            "leader_hint": jnp.where(current, snd, s["leader_hint"]),
            "heard": jnp.where(current, 1, s["heard"]),
        }, current

    def on_append(actor_id, s, snd, msg):
        term, prev, prev_term, leader_commit, count = (
            msg[1], msg[2], msg[3], msg[4], msg[5]
        )
        s = step_down(s, term)
        s, current = from_leader(s, snd, term)
        base = s["log_base"]
        known = term_at(s, prev)
        ok = current & (prev <= last_index(s)) & (
            (prev < base) | (known == prev_term)
        )
        for k in range(BATCH):
            e_term, e_kind, e_value = (
                msg[APPEND_HEAD + 3 * k + j] for j in range(3)
            )
            slot = prev + 1 + k - base - 1
            use = ok & (k < count) & (slot >= 0) & (slot < L)
            same = (slot < s["log_len"]) & (at_slot(s["log_t"], slot) == e_term)
            write = use & ~same
            hit = (slots == slot) & write
            # A conflicting entry goes with all that follow it; a new one
            # is appended: either way the window ends here.
            s = {
                **s,
                "log_t": jnp.where(hit, e_term, s["log_t"]),
                "log_k": jnp.where(hit, e_kind, s["log_k"]),
                "log_v": jnp.where(hit, e_value, s["log_v"]),
                "log_len": jnp.where(write, slot + 1, s["log_len"]),
            }
        last_new = jnp.minimum(prev + count, base + L)
        s = {**s, "commit": jnp.where(
            ok, jnp.maximum(s["commit"], jnp.minimum(leader_commit, last_new)),
            s["commit"],
        )}
        out = put(
            empty_outbox(), 0, True, jnp.clip(snd, 0, n - 1), T_APPEND_REPLY,
            s["term"], ok, jnp.where(ok, last_new, last_index(s)),
        )
        return s, out, NO, NO

    def replied(actor_id, s, snd, term, ok, idx):
        s = step_down(s, term)
        mine = (s["role"] == LEADER) & (term == s["term"])
        i = jnp.clip(snd, 0, n - 1)
        here = ids == i
        good = mine & (ok != 0)
        match = jnp.where(
            here & good, jnp.maximum(s["match"], idx), s["match"]
        )
        back = jnp.maximum(jnp.minimum(s["next"] - 1, idx + 1), 1)
        nxt = jnp.where(
            here & mine, jnp.where(good, match + 1, back), s["next"]
        )
        s = {**s, "match": match, "next": nxt}
        behind = mine & (vget(nxt, i) <= last_index(s))
        return (
            s, empty_outbox(), jnp.where(behind, bit(i), 0),
            mine.astype(jnp.int32),
        )

    def on_append_reply(actor_id, s, snd, msg):
        return replied(actor_id, s, snd, msg[1], msg[2], msg[3])

    def on_snap_reply(actor_id, s, snd, msg):
        return replied(actor_id, s, snd, msg[1], jnp.int32(1), msg[2])

    def on_install(actor_id, s, snd, msg):
        term, last_idx, last_term, last_cfg, digest = (
            msg[1], msg[2], msg[3], msg[4], msg[5]
        )
        reg = msg[APPEND_HEAD : APPEND_HEAD + KEYS]
        s = step_down(s, term)
        s, current = from_leader(s, snd, term)
        take = current & (last_idx > s["log_base"])
        # Step 6: an entry with the snapshot's last index and term keeps
        # what follows it.
        keep = (last_idx <= last_index(s)) & (term_at(s, last_idx) == last_term)
        kept = shifted(s, last_idx - s["log_base"])
        gone = {**s, "log_len": jnp.int32(0)}
        new = choose(keep, kept, gone)
        reset = ~keep | (last_idx > s["applied"])
        new = {
            **new,
            "log_base": last_idx, "snap_term": last_term,
            "snap_cfg": last_cfg, "snap_digest": digest, "snap_reg": reg,
            "reg": jnp.where(reset, reg, s["reg"]),
            "digest": jnp.where(reset, digest, s["digest"]),
            "applied": jnp.where(reset, last_idx, s["applied"]),
            "ring_from": jnp.where(reset, last_idx, s["ring_from"]),
            "commit": jnp.maximum(s["commit"], last_idx),
            "snap_installed": s["snap_installed"] + 1,
        }
        if bug != "snapshot_keeps_config":
            new["base_cfg"] = last_cfg  # BUG, where left out: step 8's end
        s = choose(take, new, s)
        out = put(
            empty_outbox(), 0, True, jnp.clip(snd, 0, n - 1), T_SNAP_REPLY,
            s["term"], jnp.where(current, last_idx, 0),
        )
        return s, out, NO, NO

    def forwarded(actor_id, s, snd, msg):
        """What a client sent a non-leader goes to its LEADER_HINT, once."""
        hint = s["leader_hint"]
        fwd = (
            (s["role"] != LEADER) & (snd >= n) & (hint >= 0)
            & (hint != actor_id)
        )
        return put(
            empty_outbox(), 0, fwd, jnp.clip(hint, 0, n - 1), msg[0], msg[1],
            msg[2],
        )

    def on_client(actor_id, s, snd, msg):
        key, v = msg[1], msg[2]
        can = (s["role"] == LEADER) & (s["log_len"] < L)
        s = append_entry(
            s, can, s["term"], K_CMD, (key & (KEYS - 1)) * 65536 + (v & 0xFFFF)
        )
        return (
            s, forwarded(actor_id, s, snd, msg),
            jnp.where(can, targets(actor_id, s), 0), NO,
        )

    def on_admin(actor_id, s, snd, msg):
        op, server = msg[1], msg[2]
        sane = ((op == ADD) | (op == REMOVE)) & (server >= 0) & (server < n)
        slot = jnp.clip(server, 0, n - 1)
        member = has(s["cfg"], slot)
        change = (op == ADD) != member  # else OK, and nothing to do
        take = (
            (s["role"] == LEADER) & (s["pending"] == 0) & sane & change
        )
        s = choose(take, {
            **s, "pending": op * 8 + server,
            "catchup_goal": last_index(s),
            "catchup_round": jnp.where(op == ADD, 1, 0),
        }, s)
        return (
            s, forwarded(actor_id, s, snd, msg),
            jnp.where(take & (op == ADD), bit(slot), 0),
            take.astype(jnp.int32),
        )

    branches = [
        on_election, on_heartbeat, on_request_vote, on_vote_reply, on_append,
        on_append_reply, on_install, on_snap_reply, on_client, on_admin,
    ]

    # -- what follows every delivery ---------------------------------------
    def advance_commit(actor_id, s):
        """Fig. 3.1, leaders: the largest N of its term that a majority of
        its CFG holds; itself counted only where it is a member (4.2.2)."""
        idx = s["log_base"] + 1 + slots
        match = jnp.where(ids == actor_id, last_index(s), s["match"])
        holds = (match[None, :] >= idx[:, None]) & has(s["cfg"], ids)[None, :]
        ok = (
            (slots < s["log_len"]) & (s["log_t"] == s["term"])
            & (jnp.sum(holds, axis=1) >= quorum(s["cfg"]))
        )
        best = jnp.max(jnp.where(ok, idx, 0))
        return {**s, "commit": jnp.where(
            s["role"] == LEADER, jnp.maximum(s["commit"], best), s["commit"]
        )}

    def work_pending(actor_id, s, work):
        """Fig. 4.1 at the leader: catch the new server up (4.2.1), wait,
        append the configuration. ``work`` 2 is a heartbeat's."""
        op, server = s["pending"] // 8, s["pending"] % 8
        on = (work > 0) & (s["role"] == LEADER) & (s["pending"] != 0)
        last = last_index(s)
        adding = on & (op == ADD) & (s["catchup_round"] > 0)
        reached = jnp.where(
            server == actor_id, last, vget(s["match"], jnp.clip(server, 0, n - 1))
        ) >= s["catchup_goal"]
        fast = adding & reached & (last - s["catchup_goal"] <= BATCH)
        slow = adding & ~fast & (reached | (work == 2))
        rounds = jnp.where(
            fast, 0, jnp.where(slow, s["catchup_round"] + 1, s["catchup_round"])
        )
        goal = jnp.where(adding & reached & ~fast, last, s["catchup_goal"])
        abort = adding & (rounds > CATCHUP_ROUNDS)
        s = {
            **s, "catchup_round": jnp.where(abort, 0, rounds),
            "catchup_goal": goal,
            "pending": jnp.where(abort, 0, s["pending"]),
        }
        ready = on & ~abort & (s["catchup_round"] == 0)
        waited = s["cfg_idx"] <= s["commit"]
        if bug != "reconfig_before_noop":
            # The 2015 fix; fig. 4.1 as printed goes on without it.
            waited = waited & (term_at(s, s["commit"]) == s["term"])
        go = ready & waited & (s["log_len"] < L)
        mask = jnp.where(
            op == ADD, s["cfg"] | bit(server), s["cfg"] & ~bit(server)
        )
        s = append_entry(s, go, s["term"], K_CFG, mask)
        return {
            **s,
            "pending": jnp.where(go, 0, s["pending"]),
            "cfg": jnp.where(go, mask, s["cfg"]),
            "cfg_idx": jnp.where(go, last + 1, s["cfg_idx"]),
        }, go

    def apply_committed(actor_id, s):
        """COMMIT > APPLIED applies, all at once: the digests of the
        entries applied are a prefix polynomial in ``_P``."""
        base = s["log_base"]
        first = s["applied"] - base  # slot of the first entry to apply
        end = jnp.minimum(s["commit"], last_index(s)) - base
        due = (slots >= first) & (slots < end)
        idx = base + 1 + slots
        h = jnp.where(due, entry_hash(idx, s["log_k"], s["log_v"]), 0)
        lift = vgather(jnp.asarray(pow_np), slots - first + 1)
        digests = s["digest"] * lift + jnp.sum(
            jnp.asarray(tri_np) * h[None, :], axis=1
        )
        any_due = end > first
        digest = jnp.where(any_due, at_slot(digests, end - 1), s["digest"])
        ring_slot = jnp.arange(RING_LEN, dtype=jnp.int32)
        lands = due[None, :] & ((idx % RING_LEN)[None, :] == ring_slot[:, None])
        ring = jnp.where(
            jnp.any(lands, axis=1),
            jnp.sum(jnp.where(lands, digests[None, :], 0), axis=1), s["ring"],
        )
        cmd = due & (s["log_k"] == K_CMD)
        keys = jnp.arange(KEYS, dtype=jnp.int32)
        writes = cmd[None, :] & ((s["log_v"] // 65536)[None, :] == keys[:, None])
        newest = jnp.max(jnp.where(writes, slots[None, :], -1), axis=1)
        reg = jnp.where(
            newest >= 0,
            vgather(s["log_v"], jnp.maximum(newest, 0)) % 65536, s["reg"],
        )
        cfgs = due & (s["log_k"] == K_CFG)
        lead = s["role"] == LEADER
        out_of_one = jnp.any(cfgs & ~has(s["log_v"], actor_id))
        s = {
            **s, "digest": digest, "ring": ring, "reg": reg,
            "applied": jnp.where(any_due, base + end, s["applied"]),
            "cfg_committed": s["cfg_committed"] + jnp.where(
                lead, jnp.sum(cfgs), 0
            ),
        }
        # 4.2.2: a leader that has committed a configuration it is not in.
        return choose(lead & out_of_one, {
            **s, "role": jnp.int32(FOLLOWER), "leader_hint": jnp.int32(-1),
            "pending": jnp.int32(0), "catchup_round": jnp.int32(0),
        }, s)

    def compact(s):
        """5.1: a snapshot through APPLIED, on the server's own."""
        k = s["applied"] - s["log_base"]
        due = k >= snapshot_every
        mask, _ = latest_cfg(s, upto=s["applied"])
        new = shifted(s, k)
        new = {
            **new, "log_base": s["applied"],
            "snap_term": term_at(s, s["applied"]), "snap_cfg": mask,
            "base_cfg": mask, "snap_reg": s["reg"], "snap_digest": s["digest"],
            "compactions": s["compactions"] + 1,
        }
        return choose(due, new, s)

    def handler(actor_id, state, snd, msg):
        s = refresh_cfg(recover(unpack(state)))
        tag = jnp.clip(msg[0], 1, NUM_TAGS) - 1
        s, out, send, work = jax.lax.switch(
            tag, branches, actor_id, s, snd, msg
        )
        s = advance_commit(actor_id, s)
        s, appended = work_pending(actor_id, s, work)
        send = jnp.where(appended, targets(actor_id, s), send)
        s = refresh_cfg(compact(apply_committed(actor_id, s)))
        rows, snapshots = replication_rows(s, send)
        s = {**s, "snap_sent": s["snap_sent"] + snapshots}
        out = jnp.where(
            jnp.concatenate([rows[:, :1] != 0, jnp.zeros((1, 1), bool)]),
            jnp.concatenate([rows, jnp.zeros((1, 2 + W), jnp.int32)]), out,
        )
        return pack(s), out

    # -- invariants --------------------------------------------------------
    ring_at = lay["RING"][0]

    def invariant(states, alive):
        role, term = states[:, ROLE], states[:, TERM]
        pair = alive[:, None] & alive[None, :] & ~jnp.eye(n, dtype=bool)
        two_leaders = jnp.any(
            pair & (role[:, None] == LEADER) & (role[None, :] == LEADER)
            & (term[:, None] == term[None, :])
        )
        applied = states[:, APPLIED]
        ring = states[:, ring_at : ring_at + RING_LEN]
        r = jnp.arange(RING_LEN, dtype=jnp.int32)
        # The index whose digest slot r of a server's ring holds.
        held = applied[:, None] - (applied[:, None] - r[None, :]) % RING_LEN
        floor = jnp.maximum(applied - RING_LEN, states[:, RING_FROM])
        valid = held > floor[:, None]
        parted = jnp.any(
            pair[:, :, None] & valid[:, None, :] & valid[None, :, :]
            & (held[:, None, :] == held[None, :, :])
            & (ring[:, None, :] != ring[None, :, :])
        )
        return jnp.where(
            two_leaders, jnp.int32(1), jnp.where(parted, jnp.int32(2), 0)
        )

    progress = (
        ("committed", lambda s: jnp.max(s[:, COMMIT])),
        ("reconfigs", lambda s: jnp.max(s[:, CFG_COMMITTED])),
        ("compactions", lambda s: jnp.sum(s[:, COMPACTIONS])),
        ("snap_sent", lambda s: jnp.sum(s[:, SNAP_SENT])),
        ("snap_installed", lambda s: jnp.sum(s[:, SNAP_INSTALLED])),
        ("restores", lambda s: jnp.sum(jnp.maximum(s[:, RESTORES] - 1, 0))),
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
        timer_tags=(T_ELECTION, T_HEARTBEAT),
        tag_names=(
            "", "ElectionTimeout", "HeartbeatTimer", "RequestVote",
            "VoteReply", "AppendEntries", "AppendReply", "InstallSnapshot",
            "SnapshotReply", "ClientCmd", "Admin",
        ),
        durable=durable_words(n, L),
        spawn_count=RESTORES,
        progress=progress,
    )


#: The share of the operator's sends that are membership commands (the
#: deployment's, ``benchmarks/configs/raft7-reconfig.json``: 0.2-0.5).
MEMBERSHIP_SHARE = 0.3


class ReconfigOperator:
    """The cluster's operator and its one client, as the fuzzer's send
    generator: etcd's remove-then-add. It believes the boot configuration
    and updates its belief at each command it sends (it sees no reply: a
    fuzzed program is made before it runs); ``note_fault`` tells it who is
    down. A send is CLIENT(key, v), key uniform over the registers and v the
    send's number, or with probability ``MEMBERSHIP_SHARE`` a membership
    command: believing fewer than the boot configuration's members,
    AddServer of a server that is up and not in its belief; else
    RemoveServer of a member it knows down, else of a random member. Either
    goes to a server drawn from those it believes up. Its belief may drift
    from the cluster's; the protocol's one PENDING absorbs it."""

    def __init__(self, app: DSLApp):
        self.app = app
        self.members = app.num_actors - 2  # the boot configuration's
        self.reset()

    def reset(self) -> None:
        names = list(self.app.actor_names())
        self.belief = names[: self.members]
        self.up = names
        self.sends = 0

    def note_fault(self, op: int, name: str) -> None:
        if op == OP_START:
            if name not in self.up:
                self.up.append(name)
        elif name in self.up:
            self.up.remove(name)

    def _membership(self, rng: _random.Random):
        if len(self.belief) < self.members:
            spare = [name for name in self.up if name not in self.belief]
            if not spare:
                return None
            name = rng.choice(spare)
            self.belief.append(name)
            return ADD, name
        down = [name for name in self.belief if name not in self.up]
        name = rng.choice(down or self.belief)
        self.belief.remove(name)
        return REMOVE, name

    def generate_row(self, rng: _random.Random, alive):
        if not self.up:
            return None
        self.sends += 1
        pad = (0,) * (self.app.msg_width - 3)
        change = (
            self._membership(rng) if rng.random() < MEMBERSHIP_SHARE else None
        )
        if change is not None:
            op, name = change
            msg = (T_ADMIN, op, self.app.actor_id(name)) + pad
        else:
            msg = (T_CLIENT, rng.randrange(KEYS), self.sends) + pad
        return rng.choice(self.up), msg

    def generate(self, rng: _random.Random, alive):
        row = self.generate_row(rng, alive)
        return None if row is None else Send(row[0], constant_message(row[1]))


def reconfig_send_generator(app: DSLApp) -> ReconfigOperator:
    return ReconfigOperator(app)
