"""sweep.compactions_per_schedule (1/schedule): the snapshots the traced jobs' retired lanes' servers took of their own logs (`sweep.app.compactions`: the app's progress count of that name, `DSLApp.progress`, the sum of a lane's servers' durable ghost words COMPACTIONS, taken at the retire while spans are live) over the lanes retired (`sweep.retired`): how often a window shifted down (5.1). Each is a whole-window gather in the handler's epilogue, which every step pays for whether it is taken or not: the count says how much of that pass is real work. It describes the traffic and moves nothing, so `moves` and `better` are what the schema needs. A program, or an app, that keeps no such count gives none."""

from lib.app_counts import app_ratio


def read(obs):
    return app_ratio("compactions", "sweep.retired")
