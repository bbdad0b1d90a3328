"""Golden per-tag effect sets for the bundled app zoo (ISSUE 18).

Differential exploration (analysis/delta.py) trusts these field sets
twice over: a silently WIDENED set kills all class transfer (every edit
cones everything — a pure perf regression), and a silently NARROWED set
under-approximates the cone (an unsound skip the audit would catch only
at bench time). Pinning the exact sets makes an innocent refactor of
analysis/effects.py that drifts extraction fail loudly, here, with a
diff a human can read.

The goldens are intentionally literal — if extraction legitimately
improves (e.g. the client handler's dynamic-index log writes become
modeled), update the table IN THE SAME COMMIT and say why in its
message.
"""

import pytest

from demi_tpu.analysis.effects import analyze_dsl_app
from demi_tpu.apps.broadcast import make_broadcast_app
from demi_tpu.apps.raft import make_raft_app
from demi_tpu.apps.spark_dag import make_spark_app
from demi_tpu.apps.twopc import make_twopc_app

# fmt: off
RAFT_GOLDEN = {
    # tag: (reads, writes, or_writes); "unknown" where the analyzer
    # bails (dynamic-index log writes in append/append_reply/client).
    0: (list(range(0, 2)) + [4] + list(range(7, 22)), [0, 1, 2, 3], [29]),
    1: (list(range(0, 2)) + [4] + list(range(7, 22)), [0, 1, 2, 3], [29]),
    2: ([0, 1, 4, 5] + list(range(7, 26)), [], [29]),
    3: (list(range(0, 5)) + [6] + list(range(7, 22)), [0, 1, 2, 3, 6],
        [29]),
    4: (list(range(0, 26)), "unknown", []),
    5: (list(range(0, 22)), list(range(0, 23)), [29]),
    6: (list(range(0, 7)) + list(range(23, 29)), "unknown", []),
    7: ([0, 1] + list(range(4, 26)), "unknown", []),
}

SPARK_GOLDEN = {
    0: ([0, 1], [], []),
    1: ([0, 1], [], []),
    2: ([2, 3], [2, 3], []),
    3: ([0, 1, 2, 3], [0, 1, 2, 3], []),
}

TWOPC_GOLDEN = {
    0: ([3], [0, 1, 2, 3], []),
    1: ([3], [0, 1, 2, 3], []),
    2: ([], [0, 1], []),
    3: ([1, 2, 3], [0, 2, 3], []),
    4: ([0, 1], [0], []),
    5: ([1, 3], [0, 3], []),
}
# fmt: on


def _sets(eff, tag):
    j = eff.per_tag[tag].to_json()
    return (j["reads"], j["writes"], j["or_writes"])


@pytest.mark.parametrize(
    "make_app,golden,n_tags",
    [
        (lambda: make_raft_app(3, bug="multivote"), RAFT_GOLDEN, 7),
        (lambda: make_spark_app(3), SPARK_GOLDEN, 3),
        (lambda: make_twopc_app(3), TWOPC_GOLDEN, 5),
    ],
    ids=["raft", "spark", "twopc"],
)
def test_golden_effect_sets(make_app, golden, n_tags):
    eff = analyze_dsl_app(make_app())
    assert eff.failure is None
    assert eff.n_tags == n_tags
    assert sorted(eff.per_tag) == sorted(golden)
    for tag, (reads, writes, or_writes) in golden.items():
        assert _sets(eff, tag) == (reads, writes, or_writes), f"tag {tag}"


def test_broadcast_is_honestly_unknown():
    # The broadcast handler's state access doesn't resolve statically —
    # the analyzer must say so per-tag (unknown => delta degrades to
    # full, sound), not fabricate a narrow set.
    eff = analyze_dsl_app(make_broadcast_app(3))
    assert eff.failure is None
    for tag in eff.per_tag:
        j = eff.per_tag[tag].to_json()
        assert j["reads"] == "unknown" and j["writes"] == "unknown"


# The protocol apps whose handlers the analyzer cannot follow (a switch
# over per-tag closures that share helpers, an unrolled loop, a starred
# call): tag count, and the sentence it bails with. Their per-tag sets
# are "unknown", so differential exploration degrades to full
# re-exploration (sound); a handler that becomes analysable moves here
# from a golden table of its own.
UNMODELED = {
    # paxos (PR 44): 8 tags, Request .. Backoff; ``propose()`` is a loop
    # unrolled over the replica's window.
    "paxos": (lambda: _paxos(), 8, "loops are not modeled"),
    # raft_reconfig (PR 47): 10 tags, ElectionTimeout .. Admin, over a row
    # of 216 words (wider than any log) that the handler takes apart into
    # a dict of scalars and arrays and packs again.
    "raft_reconfig": (
        lambda: _raft_reconfig(), 10, "unsupported expression DictComp"
    ),
    # kafka (PR 52): 15 tags over two roles (a broker's four timers and
    # nine messages, the controller's session timer and its three; ISSUE 52
    # counted 13), one handler that takes a 421-word row apart into a dict
    # of arrays over the partition slots.
    "kafka": (lambda: _kafka(), 15, "unsupported expression Dict"),
}

TAG_NAMES = {
    "paxos": (
        "Request", "Propose", "Decision", "P1a", "P1b", "P2a", "P2b",
        "Backoff",
    ),
    "raft_reconfig": (
        "ElectionTimeout", "HeartbeatTimer", "RequestVote", "VoteReply",
        "AppendEntries", "AppendReply", "InstallSnapshot", "SnapshotReply",
        "ClientCmd", "Admin",
    ),
    "kafka": (
        "FetchTimer", "IsrTimer", "CheckpointTimer", "HeartbeatTimer",
        "SessionTimer", "Register", "Heartbeat", "LeaderAndIsr", "AlterIsr",
        "AlterIsrResp", "Fetch", "FetchResp", "OffsetsForEpoch",
        "OffsetsForEpochResp", "Produce",
    ),
}


def _paxos():
    from demi_tpu.apps.paxos import make_paxos_app

    return make_paxos_app(11, log_cap=4, bug="count_replies")


def _kafka():
    from demi_tpu.apps.kafka import make_kafka_app

    app = make_kafka_app(6, log_cap=24, bug="truncate_to_hw")
    assert app.state_width == 421 and app.unkillable == (5,)
    return app


def _raft_reconfig():
    from demi_tpu.apps.raft_reconfig import make_raft_reconfig_app

    app = make_raft_reconfig_app(7, log_cap=32, bug="snapshot_keeps_config")
    assert app.state_width == 216
    return app


@pytest.mark.parametrize("name", sorted(UNMODELED))
def test_an_unmodeled_app_says_so_and_fabricates_nothing(name):
    make_app, n_tags, sentence = UNMODELED[name]
    app = make_app()
    assert len(app.tag_names) - 1 == n_tags
    assert app.tag_names[1:] == TAG_NAMES[name]
    eff = analyze_dsl_app(app)
    assert eff.n_tags == n_tags
    assert eff.failure is not None and sentence in str(eff.failure)
    for tag in eff.per_tag:
        j = eff.per_tag[tag].to_json()
        assert j["reads"] == "unknown" and j["writes"] == "unknown"


def test_refactor_edit_moves_code_not_effects():
    # The config-17 benched edit shape: a behavior-identical refactor
    # must keep every (reads, writes, or_writes) golden set EQUAL while
    # moving the edited tag's code digest — that is the entire premise
    # of a one-tag change cone.
    base = analyze_dsl_app(make_raft_app(3, bug="multivote"))
    edited = analyze_dsl_app(
        make_raft_app(3, bug="multivote", handler_edit="refactor:heartbeat")
    )
    assert edited.failure is None
    assert sorted(base.per_tag) == sorted(edited.per_tag)
    for tag in base.per_tag:
        assert (
            base.per_tag[tag].to_json() == edited.per_tag[tag].to_json()
        ), f"tag {tag}"
    assert base.tag_code[2] != edited.tag_code[2]
    for tag in base.tag_code:
        if tag != 2:
            assert base.tag_code[tag] == edited.tag_code[tag], f"tag {tag}"
    assert base.shared_code == edited.shared_code


def test_opaque_edit_degrades_to_unknown():
    # An opaque wrapper (a while-loop the analyzer cannot see through)
    # must turn the app's effects unknown — differential exploration
    # then refuses to transfer anything.
    eff = analyze_dsl_app(
        make_raft_app(3, bug="multivote", handler_edit="opaque:heartbeat")
    )
    assert eff.failure is not None or not eff.per_tag
