"""sweep.isr_changes_per_schedule (1/schedule): the in-sync-set changes the traced jobs' retired lanes' leaders had granted (`sweep.app.isr_changes`: the app's progress count of that name, `DSLApp.progress`, the sum over a lane's brokers and partitions of the durable ghost words ISR_SHRUNK and ISR_GROWN, counted by the leader when the controller's compare-and-set answered ok, taken at the retire while spans are live) over the lanes retired (`sweep.retired`): how often a schedule shrinks an ISR under a lagging or dead follower and grows it again, the changes during which the maximal ISR bounds the high watermark. It describes the traffic and moves nothing, so `moves` and `better` are what the schema needs. A program, or an app, that keeps no such count gives none."""

from lib.app_counts import app_ratio


def read(obs):
    return app_ratio("isr_changes", "sweep.retired")
