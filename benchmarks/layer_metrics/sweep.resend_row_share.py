"""sweep.resend_row_share (%): the FWD rows the traced jobs' retired lanes sent in RECONNECT bursts (`sweep.app.resent`: the app's progress count of that name, `DSLApp.progress`, summed at the retire while spans are live; chain replication's resend of `Sent` after a middle failure and its copy of `Hist` to a new tail) over the rows they put in their pools (`sweep.rows_inserted`): how much of the traffic is repair bursts on one FIFO link, the inserts that take the full [K, P] pass. A row lost at the send counts above and not below, so it reads a little high. A program, or an app, that keeps no such count gives none."""

from lib.app_counts import app_ratio


def read(obs):
    return app_ratio("resent", "sweep.rows_inserted", percent=True)
