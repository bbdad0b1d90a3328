"""sweep.recovered_share (%): the replicas of the retired lanes that were restarted after a hard kill and are NORMAL where their schedule ends (`sweep.app.recovered`) over the restarts those lanes' replicas went through (`sweep.app.recoveries`: spawn counts less one; both the app's progress counts, `DSLApp.progress`, taken at the retire while spans are live): how often the recovery protocol runs to its end inside a schedule. A replica restarted twice counts two above and at most one below. A program, or an app, that keeps no such count gives none."""

from lib.app_counts import app_ratio


def read(obs):
    return app_ratio("recovered", "sweep.app.recoveries", percent=True)
