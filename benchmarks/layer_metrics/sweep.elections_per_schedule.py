"""sweep.elections_per_schedule (1/schedule): the partition leaderships the traced jobs' retired lanes' brokers took (`sweep.app.elections`: the app's progress count of that name, `DSLApp.progress`, the sum over a lane's brokers and partitions of the durable ghost word ELECTED, counted by the broker that a LEADER_AND_ISR of a newer epoch named leader, taken at the retire while spans are live) over the lanes retired (`sweep.retired`): how often a schedule's hard kills, session expiries and re-registrations move a partition's leader, each one an epoch in which followers truncate. A sweep that elects nobody checks nothing of KIP-101; like `sweep.commits_per_schedule` it describes the traffic and moves nothing, so `moves` and `better` are what the schema needs. A program, or an app, that keeps no such count gives none."""

from lib.app_counts import app_ratio


def read(obs):
    return app_ratio("elections", "sweep.retired")
