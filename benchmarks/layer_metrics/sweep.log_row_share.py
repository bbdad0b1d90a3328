"""sweep.log_row_share (%): the rows the traced jobs' retired lanes sent that carry a log (`sweep.app.log_rows`: the app's progress count of that name, `DSLApp.progress`, summed at the retire while spans are live; VSR's DOVIEWCHANGE, STARTVIEW, NEWSTATE and a primary's RECOVERYRESPONSE) over the rows they put in their pools (`sweep.rows_inserted`): how much of the insert's pass over the W payload columns carries a log and not 32 zeros. A row lost at the send counts above and not below, so it reads a little high. A program, or an app, that keeps no such count gives none."""

from lib.app_counts import app_ratio


def read(obs):
    return app_ratio("log_rows", "sweep.rows_inserted", percent=True)
