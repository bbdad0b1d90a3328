"""Raft in the dual host/device DSL: the flagship fixture.

Stands in for the reference's out-of-repo akka-raft case studies
(README.md:16, tools/rerun_experiments.sh:7 — branches raft-45..raft-66;
BASELINE.json configs 1-3). A full Raft: leader election, log replication
with conflict truncation, commit advancement — written as one jax-traceable
handler so the same definition drives the host oracle and the vmapped
device kernels.

Timer model (the reference's, WeaveActor.aj:234-335): timers are
scheduler-controlled events, not clocks. The election timer is an
always-available "timeout may fire now" self-event; delivering it consumes
it and the handler re-arms. Arbitrary timing = the scheduler's choice of
when to deliver; reset-on-heartbeat is deliberately not modeled (the
scheduler already controls timing adversarially).

Safety invariants (jitted, checked per-delivery via invariant_interval=1):
  code 1 — Election Safety: two alive leaders in the same term.
  code 2 — committed-prefix agreement: two alive nodes disagree on an
           entry both consider committed.

Seeded bugs for fuzzing (reference-style known-bug case studies, standing
in for the akka-raft raft-NN branches):
  bug="multivote"   — voted_for ignored: a node votes for every candidate
                      of the current term (voter-side two-leaders bug).
  bug="stale_vote"  — candidate counts VoteReply messages from its *older*
                      candidacies (term check missing on the tally):
                      delayed replies from term T-1 elect it in term T
                      without a real majority (candidate-side two-leaders
                      bug; needs message delay/reordering to trigger).
  bug="stale_commit"— leader counts itself twice when advancing commit,
                      committing entries without a true majority.
  bug="gap_append"  — follower drops the Log Matching precheck (prev_idx/
                      prev_term ignored): a reordered AppendEntries writes
                      a later entry over a hole, the leader's match_index
                      advances past the hole, and commit covers an entry
                      the follower never got (committed-prefix violation;
                      raft-56-class, needs message reordering).
  bug="commit_beyond"— follower adopts leader_commit without clamping to
                      its own log length: a heartbeat reordered ahead of
                      its AppendEntries commits an entry the follower
                      doesn't have yet (committed-prefix violation).
  bug="dyn_quorum"  — quorum computed from *discovered* membership (the
                      heard-from bitmask) instead of the configured
                      cluster size: a node electing before any peer
                      exchange sees a 1-node cluster and instantly wins
                      (raft-58-initialization-class bug; two such nodes =
                      two same-term leaders).

One more case study needs NO bug flag: this fixture keeps voted_for/term
in memory only (it declares no ``DSLApp.durable`` word, and the
``raft5-nemesis`` cell rests on that), so HardKill+restart wipes
them and a restarted voter can grant a second vote in a term it already
voted in — two same-term leaders (raft-66-class lost-durability bug;
tests/test_raft_case_studies.py::test_lost_vote_durability_on_crash_recovery,
found by crash-recovery fuzzing with bounded WaitQuiescence budgets).
"""

from __future__ import annotations

import random as _random
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..dsl import DSLApp, row_set, seg_set, vgather, vget, vset
from .common import DSLSendGenerator

# Message tags.
T_ELECTION = 1  # timer
T_HEARTBEAT = 2  # timer
T_REQ_VOTE = 3  # (tag, term, last_log_idx, last_log_term)
T_VOTE_REPLY = 4  # (tag, term, granted)
T_APPEND = 5  # (tag, term, prev_idx, prev_term, leader_commit, ent_term, ent_val)
T_APPEND_REPLY = 6  # (tag, term, success, match_idx)
T_CLIENT = 7  # (tag, 0, value)

MSG_W = 7

# Roles.
FOLLOWER, CANDIDATE, LEADER = 0, 1, 2

# State layout.
ROLE = 0
TERM = 1
VOTED_FOR = 2  # -1 = none
VOTES = 3  # bitmask of granted votes (candidate)
LOG_LEN = 4
COMMIT = 5  # index of highest committed entry, -1 = none
LEADER_HINT = 6  # believed current leader (-1 unknown) for client routing
LOG_START = 7  # LOG_CAP x (term, value) interleaved


def _edit_refactor(fn):
    """Behavior- and effect-identical rewrite of one handler branch: a
    plain delegating wrapper. The branch's code digest moves; its
    read/write field sets do not — the differential explorer's
    happy path (cone = the edited tag only)."""

    def branch(actor_id, state, snd, msg):
        return fn(actor_id, state, snd, msg)

    return branch


def _edit_opaque(fn):
    """An edit the static effects analyzer cannot see through: a
    ``while`` loop makes the AST interpreter bail, degrading the app's
    effects to unknown (the differential explorer must then fall back
    to full re-exploration). The loop body runs exactly once, so the
    branch stays JAX-traceable and behavior-identical."""

    def branch(actor_id, state, snd, msg):
        first = True
        while first:
            first = False
            out = fn(actor_id, state, snd, msg)
        return out

    return branch


_EDIT_WRAPPERS = {"refactor": _edit_refactor, "opaque": _edit_opaque}

_EDIT_TAGS = {
    "election": T_ELECTION,
    "heartbeat": T_HEARTBEAT,
    "request_vote": T_REQ_VOTE,
    "vote_reply": T_VOTE_REPLY,
    "append": T_APPEND,
    "append_reply": T_APPEND_REPLY,
    "client": T_CLIENT,
}


def _parse_handler_edit(spec: str):
    """``"refactor"`` / ``"opaque"`` with an optional ``:tag`` suffix
    (name or 1-based tag int; default: the RequestVote tag, whose
    field sets the static analyzer fully resolves)."""
    kind, _, target = str(spec).partition(":")
    if kind not in _EDIT_WRAPPERS:
        raise ValueError(f"unknown handler_edit kind {kind!r}")
    tag = T_REQ_VOTE
    if target:
        tag = _EDIT_TAGS.get(target) or int(target)
    if not 1 <= tag <= 7:
        raise ValueError(f"handler_edit tag {tag} out of range 1..7")
    return _EDIT_WRAPPERS[kind], tag


def state_width(n: int, log_cap: int) -> int:
    # + next_index[n] + match_index[n] + heard-from bitmask
    return LOG_START + 2 * log_cap + 2 * n + 1


def make_raft_app(
    num_actors: int,
    log_cap: int = 8,
    bug: Optional[str] = None,
    name: str = "r",
    handler_edit: Optional[str] = None,
) -> DSLApp:
    n = num_actors
    assert n >= 2, "raft fixture requires >= 2 nodes"
    assert n <= 30, "votes bitmask is int32"
    S = state_width(n, log_cap)
    NEXT = LOG_START + 2 * log_cap
    MATCH = NEXT + n
    HEARD = MATCH + n  # bitmask of peers this node has received from
    majority = n // 2 + 1

    def init_state(actor_id: int) -> np.ndarray:
        s = np.zeros(S, np.int32)
        s[VOTED_FOR] = -1
        s[COMMIT] = -1
        s[LEADER_HINT] = -1
        return s

    def initial_msgs(actor_id: int) -> np.ndarray:
        rows = np.zeros((1, 2 + MSG_W), np.int32)
        rows[0, 0] = 1  # valid
        rows[0, 1] = actor_id  # dst = self
        rows[0, 2] = T_ELECTION
        return rows

    # -- helpers (all traced) ---------------------------------------------
    def log_term_at(state, idx):
        """Term of log entry idx; 0 when idx == -1 (empty prefix)."""
        safe = jnp.clip(idx, 0, log_cap - 1)
        t = vget(state, LOG_START + 2 * safe)
        return jnp.where(idx < 0, jnp.int32(0), t)

    def last_log(state):
        lli = state[LOG_LEN] - 1
        return lli, log_term_at(state, lli)

    def empty_outbox():
        return jnp.zeros((n, 2 + MSG_W), jnp.int32)

    def broadcast(actor_id, tag, term, a=0, b=0, c=0, d=0, e=0):
        """Rows sending (tag,...) to every other node."""
        dsts = jnp.arange(n, dtype=jnp.int32)
        valid = (dsts != actor_id).astype(jnp.int32)
        zeros = jnp.zeros(n, jnp.int32)
        return jnp.stack(
            [valid, dsts, zeros + tag, zeros + term, zeros + a, zeros + b,
             zeros + c, zeros + d, zeros + e],
            axis=1,
        )

    def one_row(outbox, slot, dst, tag, term, a=0, b=0, c=0, d=0, e=0, valid=True):
        row = jnp.stack(
            [jnp.asarray(valid, jnp.int32), dst, tag, term, a, b, c, d, e]
        ).astype(jnp.int32)
        return row_set(outbox, slot, row, valid)

    def maybe_step_down(state, term):
        """Adopt a newer term as follower (votes + leader hint cleared)."""
        newer = term > state[TERM]
        state = vset(state, TERM, jnp.where(newer, term, state[TERM]))
        state = vset(state, ROLE, jnp.where(newer, FOLLOWER, state[ROLE]))
        state = vset(state, VOTED_FOR, jnp.where(newer, -1, state[VOTED_FOR]))
        state = vset(state, VOTES, jnp.where(newer, 0, state[VOTES]))
        state = vset(state, LEADER_HINT, jnp.where(newer, -1, state[LEADER_HINT]))
        return state

    def heartbeat_rows(actor_id, state):
        """AppendEntries to every follower: the entry at next_index[i] when
        one exists, else an empty heartbeat. One entry per message (bounded
        payloads; SURVEY.md §7.3)."""
        dsts = jnp.arange(n, dtype=jnp.int32)
        next_idx = state[NEXT : NEXT + n]
        prev_idx = next_idx - 1
        safe_prev = jnp.clip(prev_idx, 0, log_cap - 1)
        prev_term = jnp.where(
            prev_idx < 0, 0, vgather(state, LOG_START + 2 * safe_prev)
        )
        has_entry = next_idx < state[LOG_LEN]
        safe_next = jnp.clip(next_idx, 0, log_cap - 1)
        ent_term = jnp.where(
            has_entry, vgather(state, LOG_START + 2 * safe_next), 0
        )
        ent_val = jnp.where(
            has_entry, vgather(state, LOG_START + 2 * safe_next + 1), 0
        )
        valid = (dsts != actor_id).astype(jnp.int32)
        zeros = jnp.zeros(n, jnp.int32)
        return jnp.stack(
            [valid, dsts, zeros + T_APPEND, zeros + state[TERM], prev_idx,
             prev_term, zeros + state[COMMIT], ent_term, ent_val],
            axis=1,
        )

    # -- per-tag handlers --------------------------------------------------
    def on_election(actor_id, state, snd, msg):
        """Timeout fired: non-leaders start a candidacy; always re-arm."""
        is_leader = state[ROLE] == LEADER
        new_term = state[TERM] + 1
        cand = state
        cand = vset(cand, ROLE, CANDIDATE)
        cand = vset(cand, TERM, new_term)
        cand = vset(cand, VOTED_FOR, actor_id)
        cand = vset(cand, VOTES, jnp.int32(1) << actor_id)
        state = jnp.where(is_leader, state, cand)

        lli, llt = last_log(state)
        rv = broadcast(actor_id, T_REQ_VOTE, state[TERM], a=lli, b=llt)
        out = jnp.where(is_leader, jnp.zeros_like(rv), rv)
        wins_alone = jnp.bool_(False)
        if bug == "dyn_quorum":
            # BUG (raft-58-initialization class): quorum is computed from
            # the nodes this one has *discovered* (heard from), not the
            # configured cluster size. A node whose election timer fires
            # before it has heard from anyone sees a 1-node "cluster",
            # wins its own vote instantly, and two such nodes elect two
            # same-term leaders.
            known = jnp.sum(
                (state[HEARD] >> jnp.arange(n, dtype=jnp.int32)) & 1
            )
            wins_alone = ~is_leader & (1 >= known // 2 + 1)
            state = jnp.where(
                wins_alone, _become_leader(actor_id, state), state
            )
            out = jnp.where(
                wins_alone,
                _arm_heartbeat(actor_id, heartbeat_rows(actor_id, state)),
                out,
            )
        # Re-arm the election timer in the self slot (broadcast never
        # targets self, so that row is free; an instant dyn_quorum winner
        # keeps its heartbeat arm there instead).
        out = one_row(out, actor_id, jnp.int32(actor_id), jnp.int32(T_ELECTION),
                      jnp.int32(0), valid=~wins_alone)
        return state, out

    def _become_leader(actor_id, state):
        st = vset(state, ROLE, LEADER)
        # next_index = log_len for all; match_index self = log_len-1, others -1.
        st = seg_set(st, NEXT, jnp.full((n,), st[LOG_LEN], jnp.int32))
        match = vset(jnp.full((n,), -1, jnp.int32), actor_id, st[LOG_LEN] - 1)
        st = seg_set(st, MATCH, match)
        return st

    def _arm_heartbeat(actor_id, outbox):
        """Overwrite own slot with a heartbeat-timer arm (self row is unused
        by broadcasts, which never target self)."""
        return one_row(outbox, actor_id, jnp.int32(actor_id),
                       jnp.int32(T_HEARTBEAT), jnp.int32(0))

    def on_heartbeat(actor_id, state, snd, msg):
        is_leader = state[ROLE] == LEADER
        out = heartbeat_rows(actor_id, state)
        out = jnp.where(is_leader, out, jnp.zeros_like(out))
        # Re-arm only while leader (a consumed timer of a deposed leader
        # stays dead until re-election arms a fresh one).
        out = jnp.where(is_leader, _arm_heartbeat(actor_id, out), out)
        return state, out

    def on_request_vote(actor_id, state, snd, msg):
        term, lli, llt = msg[1], msg[2], msg[3]
        state = maybe_step_down(state, term)
        my_lli, my_llt = last_log(state)
        log_ok = (llt > my_llt) | ((llt == my_llt) & (lli >= my_lli))
        if bug == "multivote":
            free_vote = jnp.bool_(True)  # BUG: voted_for ignored
        else:
            free_vote = (state[VOTED_FOR] == -1) | (state[VOTED_FOR] == snd)
        grant = (term == state[TERM]) & (state[ROLE] == FOLLOWER) & free_vote & log_ok
        state = vset(state, VOTED_FOR,
            jnp.where(grant, snd, state[VOTED_FOR])
        )
        out = one_row(empty_outbox(), 0, snd, jnp.int32(T_VOTE_REPLY),
                      state[TERM], a=grant.astype(jnp.int32))
        return state, out

    def on_vote_reply(actor_id, state, snd, msg):
        term, granted = msg[1], msg[2]
        state = maybe_step_down(state, term)
        if bug == "stale_vote":
            # BUG: tally ignores which candidacy the reply belongs to.
            count = (state[ROLE] == CANDIDATE) & (granted != 0)
        else:
            count = (
                (state[ROLE] == CANDIDATE) & (term == state[TERM]) & (granted != 0)
            )
        votes = jnp.where(
            count, state[VOTES] | (jnp.int32(1) << snd), state[VOTES]
        )
        state = vset(state, VOTES, votes)
        popcount = jnp.sum(
            (votes[None] >> jnp.arange(n, dtype=jnp.int32)) & 1
        )
        wins = count & (popcount >= majority)
        state = jnp.where(wins, _become_leader(actor_id, state), state)
        out = jnp.where(
            wins,
            _arm_heartbeat(actor_id, heartbeat_rows(actor_id, state)),
            empty_outbox(),
        )
        return state, out

    def on_append(actor_id, state, snd, msg):
        term, prev_idx, prev_term, leader_commit, ent_term, ent_val = (
            msg[1], msg[2], msg[3], msg[4], msg[5], msg[6]
        )
        state = maybe_step_down(state, term)
        current = term == state[TERM]
        # A current-term AppendEntries deposes a same-term candidate and
        # names the current leader.
        state = vset(state, ROLE,
            jnp.where(current & (state[ROLE] == CANDIDATE), FOLLOWER, state[ROLE])
        )
        state = vset(state, LEADER_HINT,
            jnp.where(current, snd, state[LEADER_HINT])
        )
        if bug == "gap_append":
            prev_ok = jnp.bool_(True)  # BUG: Log Matching precheck dropped
        else:
            prev_ok = (prev_idx < state[LOG_LEN]) & (
                log_term_at(state, prev_idx) == prev_term
            )
        ok = current & prev_ok
        has_entry = ent_term != 0
        write_idx = prev_idx + 1
        can_write = ok & has_entry & (write_idx < log_cap)
        # Raft truncation rule (evaluated BEFORE the write): only a
        # *conflicting* existing entry (same index, different term)
        # truncates the suffix; a same-term existing entry is identical
        # (Log Matching) so the longer log is kept, and plain heartbeats
        # never truncate.
        had_existing = write_idx < state[LOG_LEN]
        existing_term = log_term_at(state, write_idx)
        conflict = had_existing & (existing_term != ent_term)
        safe_w = jnp.clip(write_idx, 0, log_cap - 1)
        state = vset(state, LOG_START + 2 * safe_w, ent_term, can_write)
        state = vset(state, LOG_START + 2 * safe_w + 1, ent_val, can_write)
        state = vset(state, LOG_LEN,
            jnp.where(
                can_write,
                jnp.where(conflict | ~had_existing, write_idx + 1, state[LOG_LEN]),
                state[LOG_LEN],
            )
        )
        if bug == "commit_beyond":
            # BUG: commit adopted from any current-term leader message,
            # before the Log Matching check and unclamped — commits entries
            # this follower hasn't received.
            new_commit = jnp.where(
                current, jnp.maximum(state[COMMIT], leader_commit), state[COMMIT]
            )
        else:
            new_commit = jnp.where(
                ok,
                jnp.maximum(state[COMMIT],
                            jnp.minimum(leader_commit, state[LOG_LEN] - 1)),
                state[COMMIT],
            )
        state = vset(state, COMMIT, new_commit)
        match = jnp.where(ok, jnp.where(has_entry & can_write, write_idx, prev_idx), -1)
        out = one_row(empty_outbox(), 0, snd, jnp.int32(T_APPEND_REPLY),
                      state[TERM], a=ok.astype(jnp.int32), b=match)
        return state, out

    def on_append_reply(actor_id, state, snd, msg):
        term, success, match_idx = msg[1], msg[2], msg[3]
        state = maybe_step_down(state, term)
        relevant = (state[ROLE] == LEADER) & (term == state[TERM])
        nexts = state[NEXT : NEXT + n]
        matches = state[MATCH : MATCH + n]
        ok = relevant & (success != 0)
        fail = relevant & (success == 0)
        prev_match = vget(matches, snd)
        new_match = jnp.maximum(prev_match, match_idx)
        matches = vset(matches, snd, new_match, ok)
        nexts = vset(
            nexts, snd,
            jnp.where(ok, new_match + 1, jnp.maximum(vget(nexts, snd) - 1, 0)),
        )
        nexts = jnp.where(relevant, nexts, state[NEXT : NEXT + n])
        state = seg_set(state, NEXT, nexts)
        state = seg_set(state, MATCH, matches)
        # Commit advancement: highest i with log_term[i]==term replicated on
        # a majority. (bug="stale_commit": self counted twice.)
        matches = vset(matches, actor_id, state[LOG_LEN] - 1)
        idxs = jnp.arange(log_cap, dtype=jnp.int32)
        terms = state[LOG_START : LOG_START + 2 * log_cap].reshape(
            log_cap, 2
        )[:, 0]
        repl_count = jnp.sum(
            (matches[None, :] >= idxs[:, None]).astype(jnp.int32), axis=1
        )
        if bug == "stale_commit":
            repl_count = repl_count + 1  # BUG: leader double-counted
        committable = (
            (idxs < state[LOG_LEN])
            & (terms == state[TERM])
            & (repl_count >= majority)
        )
        best = jnp.max(jnp.where(committable, idxs, -1))
        state = vset(state, COMMIT,
            jnp.where(relevant, jnp.maximum(state[COMMIT], best), state[COMMIT])
        )
        return state, empty_outbox()

    def on_client(actor_id, state, snd, msg):
        value = msg[2]
        can = (state[ROLE] == LEADER) & (state[LOG_LEN] < log_cap)
        idx = jnp.clip(state[LOG_LEN], 0, log_cap - 1)
        state = vset(state, LOG_START + 2 * idx, state[TERM], can)
        state = vset(state, LOG_START + 2 * idx + 1, value, can)
        state = vset(state, LOG_LEN,
            jnp.where(can, state[LOG_LEN] + 1, state[LOG_LEN])
        )
        # Leader's own match_index tracks its log.
        state = vset(state, MATCH + actor_id, state[LOG_LEN] - 1, can)
        # Replicate eagerly (standard Raft): AppendEntries go out on append,
        # not only on the next heartbeat timer.
        out = jnp.where(
            can, heartbeat_rows(actor_id, state), empty_outbox()
        )
        # Non-leaders forward the command to their believed leader
        # (standard client routing; forwarded copies are ordinary messages
        # the scheduler may still drop/delay/reorder).
        hint = state[LEADER_HINT]
        fwd = (state[ROLE] != LEADER) & (hint >= 0) & (hint != actor_id)
        out = one_row(
            out, 0, jnp.clip(hint, 0, n - 1), jnp.int32(T_CLIENT),
            jnp.int32(0), a=value, valid=fwd,
        )
        return state, out

    # Branch table built at make-scope (a closure cell of ``handler``)
    # so ``handler_edit`` can swap an entry, and so a per-branch edit
    # moves ``handler_fingerprint`` without touching the shared
    # dispatch prologue's digest.
    branches = [
        on_election, on_heartbeat, on_request_vote, on_vote_reply,
        on_append, on_append_reply, on_client,
    ]
    if handler_edit:
        wrap, edit_tag = _parse_handler_edit(handler_edit)
        branches[edit_tag - 1] = wrap(branches[edit_tag - 1])

    def handler(actor_id, state, snd, msg):
        # Membership discovery: remember every peer we've received from
        # (self counts; external/timer senders are masked off). Only the
        # dyn_quorum bug *reads* this, but it is tracked unconditionally so
        # the layout doesn't depend on the bug flag.
        peer_bit = jnp.where(
            (snd >= 0) & (snd < n), jnp.int32(1) << jnp.clip(snd, 0, n - 1), 0
        )
        state = vset(
            state, HEARD,
            state[HEARD] | peer_bit | (jnp.int32(1) << actor_id),
        )
        tag = jnp.clip(msg[0], 1, 7) - 1
        return jax.lax.switch(
            tag, branches, actor_id, state, snd, msg
        )

    # -- invariants --------------------------------------------------------
    def invariant(states, alive):
        roles = states[:, ROLE]
        terms = states[:, TERM]
        both = alive[:, None] & alive[None, :] & ~jnp.eye(n, dtype=bool)
        two_leaders = jnp.any(
            both
            & (roles[:, None] == LEADER)
            & (roles[None, :] == LEADER)
            & (terms[:, None] == terms[None, :])
        )
        # Committed-prefix agreement.
        idxs = jnp.arange(log_cap, dtype=jnp.int32)
        logs = states[:, LOG_START : LOG_START + 2 * log_cap].reshape(n, log_cap, 2)
        commits = states[:, COMMIT]
        pair_commit = jnp.minimum(commits[:, None], commits[None, :])  # [n, n]
        in_prefix = idxs[None, None, :] <= pair_commit[:, :, None]  # [n, n, cap]
        differs = jnp.any(logs[:, None] != logs[None, :], axis=-1)  # [n, n, cap]
        log_mismatch = jnp.any(both[:, :, None] & in_prefix & differs)
        return jnp.where(
            two_leaders, jnp.int32(1), jnp.where(log_mismatch, jnp.int32(2), 0)
        )

    return DSLApp(
        name=name,
        num_actors=n,
        state_width=S,
        msg_width=MSG_W,
        max_outbox=n,
        init_state=init_state,
        handler=handler,
        initial_msgs=initial_msgs,
        invariant=invariant,
        timer_tags=(T_ELECTION, T_HEARTBEAT),
        tag_names=("", "ElectionTimeout", "HeartbeatTimer", "RequestVote",
                   "VoteReply", "AppendEntries", "AppendReply", "ClientCmd"),
    )


def raft_send_generator(app: DSLApp) -> DSLSendGenerator:
    """External client commands with distinct values."""

    def make_msg(rng: _random.Random, counter: int):
        return (T_CLIENT, 0, counter) + (0,) * (MSG_W - 3)

    return DSLSendGenerator(app, make_msg)
