"""sweep.snapshot_installs_per_schedule (1/schedule): the snapshots the traced jobs' retired lanes' servers installed (`sweep.app.snap_installed`: the app's progress count of that name, `DSLApp.progress`, the sum of a lane's servers' durable ghost words SNAP_INSTALLED, taken at the retire while spans are live) over the lanes retired (`sweep.retired`): how often a follower or a joining spare was so far behind that the leader's window no longer held what it lacked and its state machine was installed, not replayed (fig. 5.3). It describes the traffic and moves nothing, so `moves` and `better` are what the schema needs. A program, or an app, that keeps no such count gives none."""

from lib.app_counts import app_ratio


def read(obs):
    return app_ratio("snap_installed", "sweep.retired")
