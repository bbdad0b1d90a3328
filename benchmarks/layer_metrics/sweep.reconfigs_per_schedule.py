"""sweep.reconfigs_per_schedule (1/schedule): the configuration entries the traced jobs' retired lanes committed (`sweep.app.reconfigs`: the app's progress count of that name, `DSLApp.progress`, the largest of a lane's servers' durable ghost words CFG_COMMITTED, each counted by the leader that applied the entry, taken at the retire while spans are live) over the lanes retired (`sweep.retired`): how many single-server membership changes (fig. 4.1) a schedule carries from the operator's command through catch-up to commitment. A sweep that reconfigures nothing checks nothing of ch. 4; like `sweep.commits_per_schedule` it describes the traffic and moves nothing, so `moves` and `better` are what the schema needs. A program, or an app, that keeps no such count gives none."""

from lib.app_counts import app_ratio


def read(obs):
    return app_ratio("reconfigs", "sweep.retired")
