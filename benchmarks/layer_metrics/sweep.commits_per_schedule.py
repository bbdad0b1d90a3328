"""sweep.commits_per_schedule (commits/schedule): the largest acknowledged count of each retired lane's servers, summed (`sweep.app.committed`: the app's progress count of that name, `DSLApp.progress`, taken at the retire while spans are live), over the lanes retired (`sweep.retired`): how many updates a schedule carries from the head to the tail's acknowledgement through its kills and repairs. A sweep that commits nothing checks nothing of the protocol; like `sweep.fault_op_share` it describes the traffic and moves nothing, so `moves` and `better` are what the schema needs. A program, or an app, that keeps no such count gives none."""

from lib.app_counts import app_ratio


def read(obs):
    return app_ratio("committed", "sweep.retired")
