"""sweep.views_per_schedule (views/schedule): the largest view number of each retired lane's replicas, summed (`sweep.app.views`: the app's progress count of that name, `DSLApp.progress`, taken at the retire while spans are live), over the lanes retired (`sweep.retired`): how many view changes a schedule gets through. A sweep that changes no view checks nothing of the protocol; like `sweep.fault_op_share` it describes the traffic and moves nothing, so `moves` and `better` are what the schema needs. A program, or an app, that keeps no such count gives none."""

from lib.app_counts import app_ratio


def read(obs):
    return app_ratio("views", "sweep.retired")
